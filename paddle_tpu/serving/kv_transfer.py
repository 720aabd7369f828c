"""KV-block export/import plane for disaggregated prefill/decode.

The fabric half of ``serving/disagg.py``: after a prefill-role replica
finishes a prompt's bucket-ladder pass, its finished KV rows already
sit in content-addressed paged blocks (``inference/paged.py`` —
registered under rolling ``chunk_digests`` by ``commit_prefix``). This
module serializes exactly those rows into a self-describing, crc-
guarded frame and lands them into ANOTHER replica's pool, registering
the same digests there — so the decode replica's ordinary admission
path (``plan_prefix`` -> full coverage -> ``alloc_slot_cached``)
admits the handed-off request with ZERO re-prefill compute.

Contract:

- **Block-aligned, digest-keyed.** A frame carries the prompt's full
  chunks (and its partially-filled tail block, under the same
  ``_partial_key`` the prefix cache uses) with their K/V rows per
  layer. Quantized pools ship int8 data AND the float32 scale rows
  together — the pair is the value; splitting them would silently
  dequantize garbage.
- **Bit-exact.** Rows cross the wire as raw host arrays of the pool's
  storage dtype; import writes them back with ``.at[block].set``. A
  round trip changes no bits, which is what keeps greedy decode on the
  importing replica bit-identical to co-located serving
  (tools/disagg_gate.py pins it, fp32 and int8).
- **Checkpoint-v2 framing.** ``MAGIC + crc32 + length + payload``
  (the serving/aot_cache.py discipline): a short, truncated, or
  bit-flipped frame fails loudly at the boundary — import raises
  :class:`TransferError` BEFORE touching the pool, never lands a
  partial prefix.
- **Validated before mutation.** Geometry (layers/heads/head_dim/
  block_size/kv dtype) must match the destination cache, and the
  digests are recomputed from the frame's own token ids — a frame
  whose digests do not re-derive is rejected loudly (tampered or
  mis-keyed payloads must not poison the prefix index).
- **First registration wins.** A digest already resident in the
  destination pool keeps its local block (the ``commit_prefix`` rule);
  imported duplicates are dropped, so shared-prefix traffic across
  many handoffs converges to one block per chunk.

Imported blocks land refcount-0 in the reclaimable LRU (exactly the
state a finished request's registered blocks park in), so they are
admissible by the next request and evictable under pressure — the
import is indistinguishable from "this replica prefilled the prompt
itself and the request finished" as far as the pool is concerned.

No flags and no counters here: this plane is pure mechanism. The
``FLAGS_serving_disagg`` gate, the ``serving.disagg.*`` counters, the
rpc streaming, and the fail-open ladder all live in
``serving/disagg.py`` — a disarmed pipeline never calls into here, so
flag-off stays byte-for-byte silent.
"""

from __future__ import annotations

import pickle
import struct
import zlib

import numpy as np

from ..inference.paged import _partial_key, chunk_digests

__all__ = ["TransferError", "TransferTimeout", "RelayError",
           "GeometryMismatch", "ExportedPrefix", "ImportResult",
           "export_prefix", "import_prefix", "release_import",
           "pack_frame", "unpack_frame", "geometry",
           "check_geometry", "MAGIC"]

MAGIC = b"PTPUKVT1"
_HEADER = struct.Struct(">4sQ")  # crc32 (raw big-endian) + payload len
_VERSION = 1


class TransferError(RuntimeError):
    """A KV frame was rejected: corrupt framing, geometry mismatch,
    digest mismatch, non-resident source prefix, or a destination pool
    without room. Always raised BEFORE any destination-pool mutation —
    the caller (serving/disagg.py) fails open to co-located serving."""


class TransferTimeout(TransferError):
    """The fabric timed out AFTER the frame left this host: delivery
    is UNKNOWN — the remote may have imported (or admitted) it and the
    ack was lost. Distinct from a refused dial (plain
    ``ConnectionRefusedError``: nothing was sent, retry is free).
    Retrying after THIS is safe only because both remote operations
    are idempotent — import dedups resident digests, admission dedups
    on (request_id, frame digest) — but it re-ships the frame, counted
    ``serving.disagg.dup_frames`` rather than silently merged."""


class GeometryMismatch(TransferError):
    """Two pools cannot exchange frames: block size, kv dtype, or head
    layout differ. Structured — ``who`` names the refusing site (e.g.
    ``disagg.decode.<rid>``, ``fleet_cache.pull.<rid>``, ``import``)
    and ``mismatch`` maps each differing field to ``(theirs, ours)`` —
    so the refusal is diagnosable from the exception alone. Raised
    BEFORE a frame ships whenever the counterpart pre-registered its
    geometry (``kv_geom`` in the fleet-registry payload —
    serving/fleet_cache.geometry_payload); :func:`import_prefix`'s
    frame-time validation raises it too, as the backstop for peers
    that never advertised."""

    def __init__(self, who, mismatch):
        self.who = str(who)
        self.mismatch = dict(mismatch)
        super().__init__(
            f"{self.who}: pool geometry mismatch — " + "; ".join(
                f"{k}: theirs={t!r} ours={o!r}"
                for k, (t, o) in sorted(self.mismatch.items())))


def check_geometry(local_geom, advertised, who="kv"):
    """Refuse a transfer BEFORE any frame ships: compare a
    counterpart's ADVERTISED registry geometry against this pool's.
    A missing/empty advertisement passes — a peer predating geometry
    pre-registration still gets frame-time validation — but an
    advertisement that disagrees on ANY field raises
    :class:`GeometryMismatch` naming every differing field."""
    if not advertised:
        return
    diff = {k: (advertised.get(k), local_geom[k]) for k in local_geom
            if advertised.get(k) != local_geom[k]}
    if diff:
        raise GeometryMismatch(who, diff)


class RelayError(RuntimeError):
    """The token relay refused a cursor: the decode host has no record
    of the request (it restarted mid-lease, or swept the lease as
    orphaned) or the cursor runs past its buffer. Deliberately LOUD and
    non-retryable — a stale cursor must trigger reclaim/fail-open, not
    a quiet resync that could double- or skip-emit tokens."""


class ExportedPrefix:
    """An export's host-side summary (the frame itself is ``bytes``)."""

    __slots__ = ("num_tokens", "full_chunks", "partial_len", "nbytes")

    def __init__(self, num_tokens, full_chunks, partial_len, nbytes):
        self.num_tokens = num_tokens
        self.full_chunks = full_chunks
        self.partial_len = partial_len
        self.nbytes = nbytes

    @property
    def blocks(self):
        return self.full_chunks + (1 if self.partial_len else 0)


class ImportResult:
    """What an import did to the destination pool. ``blocks`` lists
    the block ids the import freshly allocated (dedups excluded) — the
    exact set :func:`release_import` can sweep back if the handed-off
    request never admits or its lease dies."""

    __slots__ = ("num_tokens", "blocks_imported", "blocks_deduped",
                 "nbytes", "blocks")

    def __init__(self, num_tokens, blocks_imported, blocks_deduped,
                 nbytes, blocks=()):
        self.num_tokens = num_tokens
        self.blocks_imported = blocks_imported
        self.blocks_deduped = blocks_deduped
        self.nbytes = nbytes
        self.blocks = list(blocks)


# -- framing (the serving/aot_cache.py checkpoint-v2 discipline) -----------

def pack_frame(payload):
    """``MAGIC + crc32 + length + payload`` — the only bytes that ever
    cross the fabric."""
    return MAGIC + _HEADER.pack(
        zlib.crc32(payload).to_bytes(4, "big"), len(payload)) + payload


def unpack_frame(frame):
    """Validate framing and return the payload, or raise
    :class:`TransferError` naming the first check that failed (short
    frame -> magic -> length -> crc, the aot_cache load order)."""
    if not isinstance(frame, (bytes, bytearray, memoryview)):
        raise TransferError(
            f"kv frame: expected bytes, got {type(frame).__name__}")
    frame = bytes(frame)
    if len(frame) < len(MAGIC) + _HEADER.size:
        raise TransferError(
            f"kv frame: short frame ({len(frame)} bytes)")
    if frame[:len(MAGIC)] != MAGIC:
        raise TransferError("kv frame: bad magic")
    crc_b, length = _HEADER.unpack_from(frame, len(MAGIC))
    payload = frame[len(MAGIC) + _HEADER.size:]
    if len(payload) != length:
        raise TransferError(
            f"kv frame: length mismatch (header {length}, "
            f"payload {len(payload)})")
    if zlib.crc32(payload) != int.from_bytes(crc_b, "big"):
        raise TransferError("kv frame: crc mismatch")
    return payload


def geometry(cache):
    """A pool's exchange-relevant shape: what frames embed, what
    replicas pre-register in their fleet payload (``kv_geom``), and
    what :func:`check_geometry` compares. Plain JSON-serializable
    scalars — it rides heartbeat payloads verbatim."""
    return {"num_layers": cache.num_layers,
            "num_kv_heads": cache.num_kv_heads,
            "head_dim": cache.head_dim,
            "block_size": cache.block_size,
            "kv_dtype": cache.kv_dtype,
            "dtype": np.dtype(cache.dtype).name
            if not cache.quantized else "int8"}


_geometry = geometry  # pre-PR-20 internal name


# -- export ----------------------------------------------------------------

def _refuse_what_no_frame_carries(cache, what):
    """A frame carries blocks of K and V of one width a KV head. A
    cache that also holds recurrent state (``PagedKVCache.state_spec``)
    cannot be handed over in one: the state at the prefix's end is in no
    block. Nor can a latent cache (``latent_spec``): it holds one pool a
    layer, of another width."""
    if cache.state_spec is not None:
        raise ValueError(
            f"{what}: this cache holds recurrent state beside its KV "
            "pools, and a transfer frame carries K and V blocks only: "
            "the state at the prefix's end would be missing on the "
            "other side.")
    if cache.latent_spec is not None:
        raise ValueError(
            f"{what}: this cache holds latent rows (one row a token "
            "shared by all heads, in one pool a layer), and a transfer "
            "frame's geometry is K and V of one width a KV head: no "
            "frame carries such rows yet.")


def export_prefix(cache, token_ids):
    """Serialize the finished KV blocks covering ``token_ids`` out of
    ``cache`` into a crc-framed transfer frame.

    The prefix must be FULLY resident (every full chunk registered,
    plus the partial tail when the prompt is not block-aligned) — on a
    prefill replica that just ran the prompt through ``commit_prefix``
    it always is; anything less raises :class:`TransferError` (the
    blocks were evicted under pressure, and a partial handoff would
    re-prefill on the decode side, which the gate forbids).

    Returns ``(frame_bytes, ExportedPrefix)``. Pure read — refcounts,
    indices, and pools are untouched.
    """
    _refuse_what_no_frame_carries(cache, "export_prefix")
    ids = np.ascontiguousarray(np.asarray(token_ids).reshape(-1),
                               dtype=np.int64)
    plan = cache.plan_prefix(ids)
    if plan.covered_tokens != plan.num_tokens:
        raise TransferError(
            f"export: prefix not fully resident ({plan.covered_tokens}"
            f"/{plan.num_tokens} tokens covered)")
    blocks = list(plan.matched_blocks)
    partial = None
    if plan.partial_block is not None:
        parent = plan.digests[-1] if plan.digests else b""
        partial = {"len": plan.partial_len,
                   "key": _partial_key(
                       parent, ids[plan.num_tokens - plan.partial_len:])}
        blocks.append(plan.partial_block)
    idx = np.asarray(blocks, np.int32)

    def rows(pools):
        return [np.asarray(p[idx]) for p in pools]

    # a step on the engine's thread deletes the pools it is handed
    # (they are donated): read between two steps, never across one
    with cache.pool_lock:
        obj = {"version": _VERSION, "geom": _geometry(cache), "ids": ids,
               "digests": list(plan.digests), "partial": partial,
               "k": rows(cache.k_pools), "v": rows(cache.v_pools),
               "k_scales": None, "v_scales": None}
        if cache.quantized:
            obj["k_scales"] = rows(cache.k_scales)
            obj["v_scales"] = rows(cache.v_scales)
    frame = pack_frame(pickle.dumps(obj, protocol=4))
    return frame, ExportedPrefix(plan.num_tokens, plan.matched_full,
                                 plan.partial_len, len(frame))


# -- import ----------------------------------------------------------------

def _validate(obj, cache):
    """Every rejection BEFORE any pool mutation."""
    if obj.get("version") != _VERSION:
        raise TransferError(
            f"import: unsupported frame version {obj.get('version')!r}")
    want, got = geometry(cache), obj.get("geom") or {}
    if got != want:
        diff = {k: (got.get(k), want[k]) for k in want
                if got.get(k) != want[k]}
        raise GeometryMismatch("import", diff)
    ids = np.ascontiguousarray(np.asarray(obj["ids"]).reshape(-1),
                               dtype=np.int64)
    digests = chunk_digests(ids, cache.block_size)
    if digests != list(obj["digests"]):
        raise TransferError(
            "import: digest mismatch (frame digests do not re-derive "
            "from its token ids)")
    partial = obj.get("partial")
    rem = ids.size - len(digests) * cache.block_size
    if partial is not None:
        parent = digests[-1] if digests else b""
        key = _partial_key(parent, ids[ids.size - int(partial["len"]):])
        if int(partial["len"]) != rem or key != partial["key"]:
            raise TransferError(
                "import: partial-tail key mismatch")
    elif rem:
        raise TransferError(
            f"import: frame covers {len(digests) * cache.block_size} of "
            f"{ids.size} tokens (missing partial tail)")
    n_rows = len(digests) + (1 if partial is not None else 0)
    for name in ("k", "v") + (("k_scales", "v_scales")
                              if cache.quantized else ()):
        rows = obj.get(name)
        if (not isinstance(rows, list) or len(rows) != cache.num_layers
                or any(r.shape[0] != n_rows for r in rows)):
            raise TransferError(f"import: malformed {name} rows")
    return ids, digests, partial, n_rows


def import_prefix(cache, frame):
    """Land a transfer frame's blocks into ``cache`` and register their
    digests, so the next ``plan_prefix`` over the same prompt reports
    full coverage and ``alloc_slot_cached`` maps the imported blocks
    read-only — zero re-prefill.

    All-or-nothing: framing, geometry, and digests are validated and
    every needed block is allocated BEFORE the first row lands; any
    failure raises :class:`TransferError` with the destination pool
    exactly as it was. Digests already resident are deduped (their
    local block wins). Returns :class:`ImportResult`.
    """
    _refuse_what_no_frame_carries(cache, "import_prefix")
    payload = unpack_frame(frame)
    try:
        obj = pickle.loads(payload)
    except Exception as e:  # noqa: BLE001 — crc passed but the pickle
        # is still hostile/garbled: same loud rejection as bad framing
        raise TransferError(f"import: undecodable payload ({e!r})") \
            from e
    ids, digests, partial, n_rows = _validate(obj, cache)

    # plan the landing: (payload row index, digest-or-key) needing a
    # fresh block vs already-resident dedups
    land = []     # (row_index, kind, key)
    deduped = 0
    for i, d in enumerate(digests):
        if d in cache._prefix_index:
            deduped += 1
        else:
            land.append((i, "full", d))
    if partial is not None:
        if partial["key"] in cache._partial_index:
            deduped += 1
        else:
            land.append((len(digests), "part", partial["key"]))
    if len(land) > cache.num_free_blocks():
        raise TransferError(
            f"import: destination pool has {cache.num_free_blocks()} "
            f"allocatable blocks, frame needs {len(land)}")
    taken = []
    for _ in land:
        b = cache._take_block()
        if b is None:  # sliced pools can under-deliver vs the estimate
            for tb in reversed(taken):
                cache._deref_block(tb)
            raise TransferError(
                "import: destination pool exhausted mid-allocation")
        taken.append(b)
    if taken:
        src = np.asarray([i for i, _, _ in land], np.int64)
        names = ("k", "v") + (("k_scales", "v_scales")
                              if cache.quantized else ())
        cache.write_blocks(taken, tuple(
            [np.asarray(r)[src] for r in obj[name]] for name in names))
    # register, then park refcount-0 in the reclaimable LRU — byte-for-
    # byte the state commit_prefix + free_slot leaves local blocks in
    for (_, kind, key), b in zip(land, taken):
        idx = cache._prefix_index if kind == "full" \
            else cache._partial_index
        idx[key] = b
        cache._block_keys.setdefault(b, []).append((kind, key))
        cache._deref_block(b)
    return ImportResult(int(ids.size), len(taken), deduped,
                        len(bytes(frame)), blocks=taken)


def release_import(cache, result):
    """Sweep a fresh import's blocks back to the TRULY-free list.

    The undo for an import whose request never made it: admission
    refused after the frame landed (serving/disagg.py fails open
    elsewhere), or the remote handoff's lease died with the blocks
    parked (orphan reclamation). Without this the refcount-0 imports
    linger in the reclaimable LRU until capacity pressure evicts them —
    correct but occupying, and invisible to "did we leak" accounting.

    Only blocks still in the EXACT state the import left them (parked
    refcount-0 in ``_cached_free``) are touched; a block another
    request admitted against, or the LRU already evicted, is skipped —
    it is no longer this import's to reclaim. Eviction goes through
    ``_drop_cached`` so ``serving.prefix.evictions`` moves and the
    digest registrations drop with the block. Returns the number of
    blocks released. Safe to call twice (second call finds nothing).
    """
    released = 0
    for b in getattr(result, "blocks", ()):
        if b in cache._cached_free and int(cache._refcount[b]) == 0:
            cache._drop_cached(b)
            cache._free.append(b)
            released += 1
    return released
