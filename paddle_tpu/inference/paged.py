"""Paged (block) KV cache + continuous batching for autoregressive decode.

Capability parity with the reference's paged-attention decode stack
(`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu:1` —
block tables over a shared KV pool — and
`masked_multihead_attention_kernel.cu` — single-token masked decode), and
the `block_multihead_attention` python API
(`python/paddle/incubate/nn/functional/block_multihead_attention.py`).

TPU-native design instead of a CUDA-kernel translation:
- The KV pool is one array per layer `[num_blocks, block_size, Hk, D]` in
  HBM; a per-slot block table `[max_batch, max_blocks_per_seq]` int32 maps
  logical token positions to pool blocks. All shapes static — the decode
  step is ONE jitted XLA program regardless of which sequences are live.
- Decode attention gathers each slot's blocks (`pool[table]`, an XLA
  gather that moves only index metadata, fused with the attention that
  follows), masks by sequence length, and runs the GQA group-folded
  attention — KV heads are never expanded.
- Block allocation/free is host-side Python (a free list): allocation is
  control flow, not compute, and stays off the device.

Continuous batching: `ContinuousBatchingEngine` keeps `max_batch` decode
slots; finished sequences free their blocks and new prompts prefill into
freed blocks while other slots keep decoding — the decode step function
never recompiles.
"""

from __future__ import annotations

import hashlib
import math
import threading
from collections import OrderedDict
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..profiler import metrics as _metrics

# pool-exhaustion preemptions (free the victim's blocks + requeue for
# re-prefill) — shared name with the serving layer's scheduler so both
# engines report under one metric
_PREEMPTS = _metrics.counter("serving.preempt")
# prefix-cache economics (docs/SERVING.md "Prefix caching"): blocks
# mapped from cache vs computed fresh at admission, copy-on-write
# copies, and LRU evictions of cold cached blocks
_PREFIX_HITS = _metrics.counter("serving.prefix.hit_blocks")
_PREFIX_MISSES = _metrics.counter("serving.prefix.miss_blocks")
_PREFIX_COW = _metrics.counter("serving.prefix.cow_copies")
_PREFIX_EVICT = _metrics.counter("serving.prefix.evictions")
# kernel-route observability (docs/OBSERVABILITY.md): which attention
# tier `paged_decode_attention` actually routed — pallas moves whenever
# the fused kernel is taken (interpret ADDITIONALLY moves when it will
# run in interpret mode, i.e. a CPU host), dense moves on the auto-mode
# dense fallback. Forced `FLAGS_paged_kernel=dense` short-circuits
# BEFORE all three (byte-for-byte revert, counter silence —
# tools/kernel_gate.py pins it). Increments happen at trace/call time:
# one movement per compiled program layer, which is exactly the "did
# the kernel route in" bit the gate asserts.
_KERN_PALLAS = _metrics.counter("serving.kernel.pallas")
_KERN_DENSE = _metrics.counter("serving.kernel.dense")
_KERN_INTERPRET = _metrics.counter("serving.kernel.interpret")
# whether the pools are updated in place (docs/OBSERVABILITY.md): every
# call of a program that writes the pools takes them donated;
# ``PagedKVCache.rebind_pools`` looks at the first pool it handed in —
# deleted means the program consumed the buffer and wrote into it, alive
# means the backend or a sharding refused the donation and the program
# wrote a copy of every pool
_KV_DONATED = _metrics.counter("serving.kv.donated_calls")
_KV_COPIED = _metrics.counter("serving.kv.copied_calls")
# bytes of recurrent state a cache holds beside its pools (0 where every
# layer of the model is attention)
_STATE_BYTES = _metrics.gauge("serving.ssm.state_bytes")
# bytes of the pools of a latent cache (one row a token a layer shared by
# every head; silent for a cache of K and V a head)
_LATENT_BYTES = _metrics.gauge("serving.mla.latent_bytes")

__all__ = ["PagedKVCache", "paged_prefill_write",
           "paged_prefill_write_masked", "paged_decode_attention",
           "paged_decode_attention_dense", "paged_decode_attention_tp",
           "paged_block_attention",
           "paged_prefix_attention_dense",
           "paged_spec_write", "paged_spec_attention_dense",
           "ContinuousBatchingEngine", "validate_request",
           "chunk_digests", "PrefixPlan", "CapacityError",
           "RecurrentStateSpec", "LatentRowSpec",
           "latent_prefill_write_masked", "latent_decode_write",
           "resolve_kv_dtype", "quant_block_ratio",
           "resolve_paged_kernel", "kernel_route"]


# ---------------------------------------------------------------------------
# Pallas kernel routing (FLAGS_paged_kernel; docs/PERF.md "Pallas
# serving-kernel tier")
# ---------------------------------------------------------------------------

_KERNEL_MODES = ("auto", "pallas", "dense")
# contexts at least this many pages long route to the chunked
# flash-decode variant (kernels/pallas/paged_attention.py) — short
# tables pay per-page grid steps that are already cheap
_CHUNK_MIN_PAGES = 16


def resolve_paged_kernel(mode=None):
    """Normalize an engine's paged-kernel routing mode (a ctor kwarg or
    the ``FLAGS_paged_kernel`` string): ``auto`` | ``pallas`` |
    ``dense``. Engines resolve ONCE at construction (the
    FLAGS_serving_prefix_cache convention) and pass the result down —
    this function never reads flags when handed an explicit mode."""
    if mode is None:
        from ..core import flags as flags_mod
        mode = flags_mod.flag("FLAGS_paged_kernel")
    m = str(mode or "auto").strip().lower()
    if m not in _KERNEL_MODES:
        raise ValueError(
            f"FLAGS_paged_kernel must be one of {_KERNEL_MODES}, "
            f"got {mode!r}")
    return m


def kernel_route(mode=None):
    """The route a resolved mode will actually take on this backend —
    ``"pallas"`` / ``"interpret"`` / ``"dense"`` — for the decode_step
    span's route attribute and the serving summary."""
    m = resolve_paged_kernel(mode)
    if m == "dense":
        return "dense"
    if m == "pallas":
        # the kernels' own interpret pick (PADDLE_PALLAS_FORCE_COMPILE
        # forces real Mosaic lowering even on a CPU host)
        from ..kernels.pallas.flash_attention import _interpret
        return "interpret" if _interpret() else "pallas"
    return "dense" if jax.default_backend() == "cpu" else "pallas"


# ---------------------------------------------------------------------------
# int8 KV block storage (FLAGS_kv_cache_dtype; docs/SERVING.md
# "Decode speed tiers")
# ---------------------------------------------------------------------------

def resolve_kv_dtype(kv_cache_dtype):
    """Normalize an engine's ``kv_cache_dtype`` setting (a ctor kwarg
    or the ``FLAGS_kv_cache_dtype`` string): ``None`` for full-
    precision pools, ``"int8"`` for quantized block storage. The cache
    itself never reads flags — engines resolve at construction (the
    FLAGS_serving_prefix_cache convention) and pass the result down."""
    v = str(kv_cache_dtype or "").strip().lower()
    if v in ("", "none", "auto", "0", "off", "false"):
        return None
    if v == "int8":
        return "int8"
    raise ValueError(
        f"kv_cache_dtype: unsupported value {kv_cache_dtype!r} "
        f"(expected '' or 'int8')")


def quant_block_ratio(head_dim, dtype):
    """Honest bytes-per-block ratio of a ``dtype`` pool over an int8
    pool INCLUDING its per-(row, head) float32 scales — the effective-
    capacity multiplier ``FLAGS_kv_cache_dtype=int8`` buys (engines
    auto-size ``num_blocks`` by it; ``serving.kv.quant.capacity_
    multiplier`` reports it). Block size and head count divide out:
    each head-row costs ``head_dim * itemsize`` bytes full-precision
    vs ``head_dim + 4`` quantized, so the ratio is ~1.9x at head_dim
    64, asymptoting to 2x as head_dim grows (the scale overhead is
    4/head_dim)."""
    return head_dim * jnp.dtype(dtype).itemsize / (head_dim + 4)


# ---------------------------------------------------------------------------
# content addressing (prefix cache)
# ---------------------------------------------------------------------------

def chunk_digests(token_ids, block_size):
    """Rolling content hashes of the FULL block-aligned chunks of
    ``token_ids`` (canonicalized to int64; padding must never reach
    here — hash real tokens only, see serving/bucketing.py). Each digest
    folds in its parent's digest, so a chunk digest identifies the
    entire prefix up to and including that chunk — two prompts share a
    digest iff they share every token before it."""
    ids = np.ascontiguousarray(np.asarray(token_ids).reshape(-1),
                               dtype=np.int64)
    out, parent = [], b""
    for c in range(ids.size // block_size):
        parent = hashlib.blake2b(
            parent + ids[c * block_size:(c + 1) * block_size].tobytes(),
            digest_size=16).digest()
        out.append(parent)
    return out


def _partial_key(parent_digest, token_ids):
    """Content key for a partially-filled tail block: the full-chunk
    parent chain plus the partial tokens themselves."""
    ids = np.ascontiguousarray(np.asarray(token_ids).reshape(-1),
                               dtype=np.int64)
    return hashlib.blake2b(parent_digest + b"|part|" + ids.tobytes(),
                           digest_size=16).digest()


class CapacityError:
    """Falsy result of a failed ``ensure_capacity``/``prepare_append``:
    tells the caller WHY growth was denied so "evict cold prefixes /
    preempt and retry" (``blocks``) is distinguishable from "this
    sequence can never fit" (``seq_limit``). Previously both collapsed
    into a bare ``False`` and straight into preemption."""

    __slots__ = ("reason", "detail")

    BLOCKS = "blocks"          # pool exhausted — reclaimable later
    SEQ_LIMIT = "seq_limit"    # max_blocks_per_seq — never fits

    def __init__(self, reason, detail=""):
        self.reason = reason
        self.detail = detail

    def __bool__(self):
        return False

    def __repr__(self):
        return f"CapacityError({self.reason!r}, {self.detail!r})"


@dataclass(frozen=True)
class RecurrentStateSpec:
    """What the layers of a model that are not attention carry from
    step to step, a slot: ``layers`` of them, each a float32 state
    ``[states, channels]`` (the channels on the lanes) and the last
    ``conv_tail`` inputs of a causal convolution ``[conv_tail,
    channels]``. A constant size a slot, whatever its length."""

    layers: int
    channels: int
    states: int
    conv_tail: int


@dataclass(frozen=True)
class LatentRowSpec:
    """What a layer of latent attention (MLA) caches of a token: ONE row
    shared by all heads, its first ``latent`` values the compressed keys
    *and* values (a head's keys and values are rebuilt from them, or
    the projection is folded into the query and the output), the next
    ``rope`` values the rotary keys. The row lies in ONE pool, ``lanes``
    wide: the compressed part, then the rotary keys in whole 128-lane
    tiles with zeros behind them (the chip lays a narrower row out so
    anyway, and a kernel's DMA moves whole tiles: 512 + 64 is 640
    lanes). One pool and not one a part, because the two parts are
    always read together and what a copy costs the kernel that starts it
    is scalar work, the same whatever the copy's size (PERF.md 6, PR
    35): a page is one copy."""

    latent: int
    rope: int

    @property
    def lanes(self):
        return self.latent + -(-self.rope // 128) * 128


class _RowLanes:
    """``k_pools[i]`` / ``v_pools[i]`` of a latent cache: lanes ``lo`` to
    ``hi`` of layer ``i``'s row pool as it stands when asked, indexed by
    blocks. ``view[table]`` gathers the pages asked for and slices their
    lanes; nothing a pool wide is made (the pools are most of the
    device's memory). For readers written against a pair of pools
    (``benchmarks/drivers/serve_latent._held_rows``), under
    ``pool_lock`` as ever; the programs take ``row_pools``."""

    def __init__(self, cache, layer, lo, hi):
        self._cache, self._layer, self._lo, self._hi = cache, layer, lo, hi

    @property
    def shape(self):
        return self._cache.row_pools[self._layer].shape[:-1] \
            + (self._hi - self._lo,)

    def __getitem__(self, blocks):
        return self._cache.row_pools[self._layer][blocks][
            ..., self._lo:self._hi]


@dataclass
class PrefixPlan:
    """Host-side admission plan from ``PagedKVCache.plan_prefix``: which
    leading chunks of a prompt are already resident (and where), and how
    much of the prompt is therefore covered. Pure data — computing a
    plan has no side effects; ``alloc_slot_cached`` consumes it."""

    ids: np.ndarray            # the (unpadded) token ids planned against
    num_tokens: int
    chunks_total: int          # ceil(num_tokens / block_size), >= 1
    digests: list              # rolling digests of the full chunks
    matched_full: int          # leading full chunks found in the index
    matched_blocks: list       # their pool block ids, in chunk order
    partial_block: int | None  # matched partially-filled tail block
    partial_len: int           # tokens matched inside it
    partial_shared: bool       # True: mapped read-only (no writes land
    #                            in it); False: copy-on-write at admit
    covered_tokens: int        # matched_full*block_size + partial_len

    @property
    def tail_start(self):
        """First token position the prefill must COMPUTE. Full coverage
        still recomputes the last token — its logits seed decoding."""
        return self.covered_tokens if self.covered_tokens \
            < self.num_tokens else self.num_tokens - 1

    @property
    def write_start(self):
        """First token position the prefill may WRITE (never a shared
        row; full coverage writes nothing)."""
        return self.covered_tokens

    @property
    def hit_blocks(self):
        return self.matched_full + (1 if self.partial_block is not None
                                    else 0)


class PagedKVCache:
    """Per-layer block pools + block tables + sequence lengths.

    Device state: k_pools/v_pools (list per layer; int8 pools carry
    k_scales/v_scales beside them). The pools are BUFFERS, updated in
    place: every program that writes them takes them donated and
    :meth:`rebind_pools` takes what it returns, under ``pool_lock`` from
    dispatch to rebind — the arrays handed in are deleted by the call,
    so a reader on another thread (``kv_transfer.export_prefix``) holds
    the lock too and nobody keeps a pool across a call. Host state:
    block_tables [max_batch, max_blocks_per_seq] int32, seq_lens
    [max_batch] int32, the free-list of block ids, per-block refcounts,
    and the content-addressed prefix index.

    **Recurrent state** (``recurrent_state``, a
    :class:`RecurrentStateSpec`; ``models/jamba.py``): the layers of a
    hybrid model that are not attention keep, a slot, a state that is not
    paged, not shared and not addressed by a block table. The cache
    holds it stacked, one array a kind — ``ssm_state`` [state layers,
    slots, states, channels] float32 and ``conv_state`` [state layers,
    conv_tail, slots, channels] in the compute type — beside pools that
    then have only the attention layers' ``num_layers``. It rides the
    same protocol: donated with the pools, returned, rebound under
    ``pool_lock``. ``alloc_slot`` marks a slot's state ``state_fresh``
    (nothing of the slot's last request may be read); a prefill writes it
    from zero. Such a cache plans no prefix hit and registers no chunk:
    the state at a prefix's end exists nowhere (docs/SERVING.md).

    **Latent rows** (``latent_rows``, a :class:`LatentRowSpec`;
    ``models/xing.py``): another pool geometry under the same tables,
    refcounts, prefix index, copy-on-write and donation. A layer holds
    ONE pool, ``row_pools[i]`` ``[blocks, page, 1, lanes]``: a token's
    compressed keys-and-values in the first ``latent`` lanes, its rotary
    keys behind them, zeros to the tile's end. There is no separate V,
    no head axis to shard, and no second pool of any size: a page is
    one copy for the decode kernel, which pays for a copy in scalar
    work whatever the copy's size (PERF.md 6, PR 35). Every
    block-level mechanism acts on the lists :meth:`pool_lists` gives and
    asks nothing of their widths or their number. ``k_pools[i][table]``
    and ``v_pools[i][table]`` still answer, with the compressed part and
    the lanes behind it of the pages asked for (:class:`_RowLanes`: a
    gather and a lane slice, never a pool), for readers written against
    the pair of pools. Prefix sharing works as for K and V (a block's
    rows are all a position's state). Refused at construction, with the
    reason: int8 pools and a serving mesh (docs/SERVING.md).

    **Prefix sharing** (vLLM shared-block / SGLang RadixAttention
    style): a block registered in the prefix index is immutable in its
    registered rows and may back several slots at once (refcount > 1).
    Appends past every sharer's seq_len are safe in place at refcount 1;
    any write to a block with refcount > 1 copies it first
    (``prepare_append`` / admission COW). ``free_slot`` only decrements
    refcounts: registered blocks that reach zero park in an LRU of
    reclaimable blocks instead of the free list, so a later identical
    prefix still hits; allocation falls back to evicting that LRU
    before it ever fails. Nothing here reads flags — an engine that
    never registers chunks (``commit_prefix``) gets byte-for-byte the
    pre-prefix-cache behavior.
    """

    def __init__(self, num_layers, num_kv_heads, head_dim, *, num_blocks,
                 block_size=16, max_blocks_per_seq, max_batch,
                 dtype=jnp.bfloat16, kv_dtype=None, pool_sharding=None,
                 scale_sharding=None, num_slices=1, recurrent_state=None,
                 latent_rows=None):
        self.latent_spec = latent_rows
        if latent_rows is not None:
            if resolve_kv_dtype(kv_dtype) == "int8":
                raise ValueError(
                    "PagedKVCache: a latent cache holds bfloat16/float32 "
                    "rows only: int8 KV (FLAGS_kv_cache_dtype=int8) "
                    "scales a row a KV head, and a latent row's rotary "
                    "part and compressed part need scales of their own, "
                    "which no kernel reads yet.")
            if pool_sharding is not None or int(num_slices) > 1:
                raise ValueError(
                    "PagedKVCache: a latent cache is held on one device: "
                    "a serving mesh shards the pools by KV head, and a "
                    "latent row has none.")
            num_kv_heads, head_dim = 1, int(latent_rows.latent)
        self.num_layers = num_layers
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_blocks_per_seq = max_blocks_per_seq
        self.max_batch = max_batch
        self.dtype = dtype
        # mesh-sharded serving (serving/mesh.py): ``pool_sharding`` /
        # ``scale_sharding`` lay the device pools out over a mesh
        # (kv-head axis split across model shards); ``num_slices``
        # (the mesh's data extent) partitions HOST capacity — slots
        # and blocks divide into slices, allocation binds a slot to
        # its slice's blocks, and occupancy() reports per-slice. At
        # the default 1 every slice helper degenerates to the legacy
        # single-pool behavior byte-for-byte.
        self.num_slices = max(int(num_slices), 1)
        if self.num_slices > max_batch:
            raise ValueError(
                f"PagedKVCache: num_slices {self.num_slices} exceeds "
                f"max_batch {max_batch} — every slice needs at least "
                f"one decode slot")
        if self.num_slices > num_blocks - 1:
            raise ValueError(
                f"PagedKVCache: num_slices {self.num_slices} exceeds "
                f"the {num_blocks - 1} usable blocks")
        if self.num_slices > 1:
            self._block_owner = np.full((num_blocks,), -1, np.int32)
            self._block_owner[1:] = (np.arange(1, num_blocks)
                                     - 1) % self.num_slices
        else:
            self._block_owner = None
        # ``kv_dtype="int8"`` (FLAGS_kv_cache_dtype, resolved by the
        # engine): pools store int8 rows with per-(token-slot, kv-head)
        # float32 absmax scales beside them (quantization.quantize_rows
        # — the AbsmaxObserver formula); ``dtype`` stays the COMPUTE
        # dtype the attention dequantizes into. Every block-level
        # mechanism (tables, refcounts, prefix index, COW, LRU) is
        # dtype-blind, so prefix sharing carries over unchanged.
        self.kv_dtype = resolve_kv_dtype(kv_dtype)
        self.quantized = self.kv_dtype == "int8"
        shape = (num_blocks, block_size, num_kv_heads, head_dim)
        store_dt = jnp.int8 if self.quantized else dtype

        def _pool(sh, dt, sharding):
            z = jnp.zeros(sh, dt)
            return z if sharding is None else jax.device_put(z, sharding)

        if latent_rows is not None:
            # one pool a layer; the pair's names read it (``_RowLanes``)
            lanes = latent_rows.lanes
            self.row_pools = [_pool(shape[:3] + (lanes,), store_dt, None)
                              for _ in range(num_layers)]
            self.k_pools = [_RowLanes(self, i, 0, head_dim)
                            for i in range(num_layers)]
            self.v_pools = [_RowLanes(self, i, head_dim, lanes)
                            for i in range(num_layers)]
        else:
            self.row_pools = None
            self.k_pools = [_pool(shape, store_dt, pool_sharding)
                            for _ in range(num_layers)]
            self.v_pools = [_pool(shape, store_dt, pool_sharding)
                            for _ in range(num_layers)]
        if self.quantized:
            sshape = (num_blocks, block_size, num_kv_heads)
            self.k_scales = [_pool(sshape, jnp.float32, scale_sharding)
                             for _ in range(num_layers)]
            self.v_scales = [_pool(sshape, jnp.float32, scale_sharding)
                             for _ in range(num_layers)]
        else:
            self.k_scales = self.v_scales = None
        self.state_spec = recurrent_state
        self.ssm_state = self.conv_state = None
        # slots whose state holds nothing of their request yet: set when
        # a slot is allocated, cleared by the prefill that writes it
        self.state_fresh = np.ones((max_batch,), bool)
        if recurrent_state is not None:
            if pool_sharding is not None or self.num_slices > 1:
                raise ValueError(
                    "PagedKVCache: recurrent state is held on one "
                    "device: a serving mesh has no sharding rule for "
                    "it.")
            st = recurrent_state
            self.ssm_state = jnp.zeros(
                (st.layers, max_batch, st.states, st.channels),
                jnp.float32)
            self.conv_state = jnp.zeros(
                (st.layers, st.conv_tail, max_batch, st.channels), dtype)
            _STATE_BYTES.set(self.state_bytes())
        if latent_rows is not None:
            _LATENT_BYTES.set(self.pool_bytes())
        # held from the dispatch of a pool-writing program until its
        # pools are rebound, and by any reader off the engine's thread
        self.pool_lock = threading.RLock()
        # block 0 is reserved as the null block so fresh table entries are
        # valid indices; the length mask hides its contents
        self._free = list(range(num_blocks - 1, 0, -1))
        # HOST-side metadata (numpy, not device arrays): block tables and
        # lengths mutate every step from python; they upload as (tiny)
        # jit-call arguments instead of paying a device .at[].set each.
        self.block_tables = np.zeros((max_batch, max_blocks_per_seq),
                                     np.int32)
        self.seq_lens = np.zeros((max_batch,), np.int32)
        self._slot_blocks = [[] for _ in range(max_batch)]
        self._live = [False] * max_batch
        # prefix-cache state (inert until commit_prefix registers chunks)
        self._refcount = np.zeros((num_blocks,), np.int32)
        self._prefix_index = {}    # full-chunk digest -> block id
        self._partial_index = {}   # partial-tail key  -> block id
        self._block_keys = {}      # block id -> [(kind, key), ...]
        self._cached_free = OrderedDict()  # refcount-0 registered, LRU

    # -- host-side management ---------------------------------------------

    @property
    def max_seq_len(self):
        return self.max_blocks_per_seq * self.block_size

    def free_slots(self):
        return [i for i, l in enumerate(self._live) if not l]

    # -- mesh capacity slices (serving/mesh.py) ----------------------------

    def slice_of_slot(self, slot):
        """The capacity slice a decode slot belongs to (contiguous,
        balanced groups); 0 for the unsliced cache."""
        return slot * self.num_slices // self.max_batch

    def _slice_of_block(self, b):
        return int(self._block_owner[b]) if self._block_owner is not None \
            else 0

    def _slice_free_count(self, slice_id):
        """Allocatable blocks (free + reclaimable-cached) owned by one
        slice — the per-slice form of :meth:`num_free_blocks`."""
        if self.num_slices <= 1:
            return len(self._free) + len(self._cached_free)
        own = self._block_owner
        return (sum(1 for b in self._free if own[b] == slice_id)
                + sum(1 for b in self._cached_free if own[b] == slice_id))

    def binding_slice(self):
        """The slice the NEXT admission would bind to — the one the
        admission/shed watermarks should read (serving/overload.py):
        among slices with a free slot, the one with the most
        allocatable blocks (lowest id on ties). None for the unsliced
        cache (aggregate semantics, byte-for-byte pre-mesh)."""
        if self.num_slices <= 1:
            return None
        free = self.free_slots()
        cand = sorted({self.slice_of_slot(s) for s in free}) if free \
            else range(self.num_slices)
        return max(cand, key=self._slice_free_count)

    def num_free_blocks(self, slice=None):
        """Blocks allocatable RIGHT NOW: truly free plus reclaimable
        cached (refcount-0 registered blocks the LRU can evict).
        ``slice`` restricts to one capacity slice's blocks."""
        if slice is None or self.num_slices <= 1:
            return len(self._free) + len(self._cached_free)
        return self._slice_free_count(slice)

    def num_cached_blocks(self):
        """Reclaimable refcount-0 blocks held only by the prefix index."""
        return len(self._cached_free)

    def num_shared_blocks(self):
        """Blocks currently backing more than one slot."""
        return int((self._refcount > 1).sum())

    def reclaimable_blocks(self, slot):
        """How many of the slot's blocks freeing it would actually
        return to the pool (refcount 1 — not shared with anyone)."""
        return sum(1 for b in self._slot_blocks[slot]
                   if self._refcount[b] == 1)

    def occupancy(self, slice=None):
        """Pool occupancy breakdown (host metadata only — no device
        reads). ``active`` blocks are pinned by live slots (refcount >
        0), ``shared`` of those back more than one slot, ``cached_free``
        are refcount-0 registered blocks the LRU can reclaim, ``free``
        are truly free. active + cached_free + free == usable always —
        per slice and in aggregate (``slice=i`` restricts to one mesh
        capacity slice's blocks; per-slice values sum EXACTLY to the
        aggregate, tests/framework/test_mesh_serving.py pins it)."""
        if slice is not None and self.num_slices > 1:
            own = self._block_owner
            usable = int((own == slice).sum())  # null block owns -1
            free = sum(1 for b in self._free if own[b] == slice)
            cached = sum(1 for b in self._cached_free if own[b] == slice)
            shared = int(((self._refcount > 1) & (own == slice)).sum())
            return {"usable": usable, "active": usable - free - cached,
                    "shared": shared, "cached_free": cached,
                    "free": free}
        usable = self.num_blocks - 1
        free = len(self._free)
        cached = len(self._cached_free)
        return {"usable": usable,
                "active": usable - free - cached,
                "shared": self.num_shared_blocks(),
                "cached_free": cached,
                "free": free}

    def state_bytes(self):
        """Bytes of recurrent state held beside the pools (0 where the
        model has none): a constant a slot, whatever is live."""
        if self.state_spec is None:
            return 0
        return int(self.ssm_state.nbytes + self.conv_state.nbytes)

    def state_args(self):
        """The recurrent state as a serving program takes it, after the
        four pool lists: one more donated argument, or none."""
        return () if self.state_spec is None \
            else ((self.ssm_state, self.conv_state),)

    def occupancy_slices(self):
        """Per-slice occupancy dicts, index == slice id (a single
        aggregate entry for the unsliced cache)."""
        if self.num_slices <= 1:
            return [self.occupancy()]
        return [self.occupancy(slice=i) for i in range(self.num_slices)]

    def pool_bytes(self, slice=None):
        """Total HBM footprint of the K+V pools and of the recurrent
        state beside them (static: allocated at construction,
        independent of occupancy). Quantized pools count
        their int8 rows PLUS the float32 scale arrays — the multiplier
        ``occupancy()`` shows must never be paid for twice in hidden
        bytes (tools/spec_gate.py pins consistency). ``slice=i``
        reports one mesh capacity slice's proportional share (by its
        usable-block count; the reserved null block rides the
        aggregate only)."""
        item = 1 if self.quantized else jnp.dtype(self.dtype).itemsize
        rows = self.num_blocks * self.block_size * self.num_kv_heads
        # K and V of a head, or a latent cache's one row
        width = 2 * self.head_dim if self.latent_spec is None \
            else self.latent_spec.lanes
        total = self.num_layers * rows * width * item
        if self.quantized:
            total += 2 * self.num_layers * rows * 4
        if slice is not None and self.num_slices > 1:
            usable = int((self._block_owner == slice).sum())
            return int(total * usable / max(self.num_blocks - 1, 1))
        return total + self.state_bytes()

    # -- block primitives --------------------------------------------------

    def _drop_cached(self, b):
        """Evict one reclaimable cached block: its prefix-index entries
        drop (the "evict cold prefixes before preempting anyone"
        rung)."""
        del self._cached_free[b]
        for kind, key in self._block_keys.pop(b, ()):
            idx = self._prefix_index if kind == "full" \
                else self._partial_index
            if idx.get(key) == b:
                del idx[key]
        _PREFIX_EVICT.inc()

    def _take_block(self, slice_id=None):
        """Allocate one block (refcount 1): the free list first, then
        LRU eviction of a cold cached block. None when both are empty.
        ``slice_id`` (sliced caches) restricts allocation to one
        capacity slice's blocks — the unsliced path is byte-for-byte
        the legacy pop/LRU order."""
        b = None
        if self.num_slices <= 1 or slice_id is None:
            if self._free:
                b = self._free.pop()
            elif self._cached_free:
                b = next(iter(self._cached_free))
                self._drop_cached(b)
        else:
            own = self._block_owner
            for i in range(len(self._free) - 1, -1, -1):
                if own[self._free[i]] == slice_id:
                    b = self._free.pop(i)
                    break
            if b is None:
                for cb in self._cached_free:  # LRU order
                    if own[cb] == slice_id:
                        b = cb
                        break
                if b is not None:
                    self._drop_cached(b)
        if b is None:
            return None
        self._refcount[b] = 1
        return b

    def _release_block(self, b):
        """A block's refcount reached zero: park it reclaimable-cached
        if the prefix index still wants it, else truly free it."""
        if self._block_keys.get(b):
            self._cached_free[b] = None  # most-recently-used end
        else:
            self._free.append(b)

    def _ref_block(self, b):
        self._refcount[b] += 1
        if b in self._cached_free:
            del self._cached_free[b]

    def _deref_block(self, b):
        self._refcount[b] -= 1
        if self._refcount[b] <= 0:
            self._refcount[b] = 0
            self._release_block(b)

    def pool_lists(self):
        """The four lists a serving program takes donated and returns,
        a layer an entry: the K pools, the V pools and an int8 cache's
        two lists of scale arrays (empty for full precision). A latent
        cache has one pool a layer: its rows come first and the other
        three lists are empty."""
        if self.latent_spec is not None:
            return self.row_pools, [], [], []
        return (self.k_pools, self.v_pools, self.k_scales or [],
                self.v_scales or [])

    def pool_arrays(self):
        """Every array a pool-writing program takes donated, list after
        list of :meth:`pool_lists`."""
        return [pool for held in self.pool_lists() for pool in held]

    def rebind_pools(self, k_pools, v_pools, k_scales=None,
                     v_scales=None, state=None):
        """Take the lists of :meth:`pool_lists` (and the recurrent
        state, where the cache holds one) as a pool-writing program
        returned them (the caller
        holds ``pool_lock`` since before the dispatch). The pools handed
        in are still bound here, so one look at the first says whether
        the program consumed them (``serving.kv.donated_calls``) or
        wrote copies (``serving.kv.copied_calls``)."""
        (_KV_DONATED if self.pool_lists()[0][0].is_deleted()
         else _KV_COPIED).inc()
        if self.latent_spec is not None:
            self.row_pools = list(k_pools)
        else:
            self.k_pools = list(k_pools)
            self.v_pools = list(v_pools)
        if self.quantized:
            self.k_scales = list(k_scales)
            self.v_scales = list(v_scales)
        if state is not None:
            self.ssm_state, self.conv_state = state

    def write_blocks(self, dst, src):
        """Overwrite pool blocks ``dst`` in every layer's pools (and
        scale arrays) through the one donated block-copy program:
        ``src`` is either block ids of the same pools (copy-on-write)
        or ``(k, v[, k_scales, v_scales])`` lists of one ``[len(dst),
        ...]`` array a layer (a transfer landing)."""
        dst = jnp.asarray(dst, jnp.int32)
        if isinstance(src, tuple):
            src = [r for rows in src for r in rows]
        else:
            src = jnp.asarray(src, jnp.int32)
        with self.pool_lock:
            out = iter(_kv_block_copy(self.pool_arrays(), dst, src))
            self.rebind_pools(*([next(out) for _ in held]
                                for held in self.pool_lists()))

    def _copy_block_rows(self, src, dst):
        """Copy-on-write body: duplicate one pool block across every
        layer (the K and V rows move together; quantized pools copy
        the scale rows with them — an int8 copy is bit-exact, so
        shared-vs-private content stays identical)."""
        self.write_blocks([dst], [src])

    def _choose_slot(self):
        """Admission slot choice: the first free slot (legacy FCFS
        order), or — sliced — the first free slot in the slice with
        the most allocatable blocks (the least-loaded-slice placement
        the per-slice watermarks read via :meth:`binding_slice`)."""
        free = self.free_slots()
        if not free:
            return None
        if self.num_slices <= 1:
            return free[0]
        best = None
        for s in free:
            cap = self._slice_free_count(self.slice_of_slot(s))
            if best is None or cap > best[0]:
                best = (cap, s)
        return best[1]

    def alloc_slot(self, num_tokens):
        """Claim a slot + enough blocks for `num_tokens` (from the
        slot's capacity slice, on a sliced cache); returns slot id
        or None if out of slots/blocks."""
        need = max(1, math.ceil(num_tokens / self.block_size))
        slot = self._choose_slot()
        if slot is None or need > self.max_blocks_per_seq:
            return None
        sl = self.slice_of_slot(slot)
        if need > self.num_free_blocks(
                sl if self.num_slices > 1 else None):
            return None
        blocks = [self._take_block(sl) for _ in range(need)]
        self._slot_blocks[slot] = blocks
        self._live[slot] = True
        row = np.zeros((self.max_blocks_per_seq,), np.int32)
        row[:need] = blocks
        self.block_tables[slot] = row
        self.seq_lens[slot] = 0
        self.state_fresh[slot] = True
        return slot

    def ensure_capacity(self, slot, new_len):
        """Grow the slot's table if `new_len` tokens need another block
        (evicting cold cached blocks if the free list is dry). Returns
        True, or a falsy :class:`CapacityError` naming WHY growth was
        denied — ``blocks`` (pool exhausted; eviction/preemption can
        help) vs ``seq_limit`` (``max_blocks_per_seq``; this sequence
        can never fit, retrying is pointless)."""
        have = len(self._slot_blocks[slot])
        need = math.ceil(new_len / self.block_size)
        while have < need:
            if have >= self.max_blocks_per_seq:
                return CapacityError(
                    CapacityError.SEQ_LIMIT,
                    f"{new_len} tokens need {need} blocks > "
                    f"max_blocks_per_seq {self.max_blocks_per_seq}")
            b = self._take_block(self.slice_of_slot(slot))
            if b is None:
                return CapacityError(
                    CapacityError.BLOCKS,
                    f"pool exhausted growing slot {slot} to {new_len} "
                    f"tokens")
            self.block_tables[slot, have] = b
            self._slot_blocks[slot].append(b)
            have += 1
        return True

    def prepare_append(self, slot, new_len):
        """Make position ``new_len - 1`` writable for this slot: grow
        the table if the position opens a new block, and copy-on-write
        the target block if it is shared (a decode append into a
        partially-filled shared block must never be visible to the
        other sharers). Returns True or a falsy :class:`CapacityError`
        (same contract as ``ensure_capacity``)."""
        r = self.ensure_capacity(slot, new_len)
        if not r:
            return r
        ci = (new_len - 1) // self.block_size
        b = self._slot_blocks[slot][ci]
        if self._refcount[b] > 1:
            nb = self._take_block(self.slice_of_slot(slot))
            if nb is None:
                return CapacityError(
                    CapacityError.BLOCKS,
                    f"pool exhausted copy-on-writing shared block {b}")
            self._copy_block_rows(b, nb)
            self._slot_blocks[slot][ci] = nb
            self.block_tables[slot, ci] = nb
            self._deref_block(b)
            _PREFIX_COW.inc()
        return True

    def prepare_append_range(self, slot, new_len):
        """Speculative-decode form of :meth:`prepare_append`: make EVERY
        position in ``[seq_len, new_len)`` writable — grow the table to
        ``ceil(new_len / block_size)`` blocks and copy-on-write every
        shared block the range touches (a draft row must never land in
        a block another slot can read). Returns True or a falsy
        :class:`CapacityError`; on error the slot's fresh growth is
        rolled back (completed COWs keep — they are content-identical
        and the plain decode path would COW them anyway)."""
        have0 = len(self._slot_blocks[slot])
        r = self.ensure_capacity(slot, new_len)
        if not r:
            self.truncate_blocks(slot, have0)
            return r
        lo = int(self.seq_lens[slot]) // self.block_size
        hi = (new_len - 1) // self.block_size
        for ci in range(lo, hi + 1):
            b = self._slot_blocks[slot][ci]
            if self._refcount[b] > 1:
                nb = self._take_block(self.slice_of_slot(slot))
                if nb is None:
                    self.truncate_blocks(slot, have0)
                    return CapacityError(
                        CapacityError.BLOCKS,
                        f"pool exhausted copy-on-writing shared block "
                        f"{b} for speculative range")
                self._copy_block_rows(b, nb)
                self._slot_blocks[slot][ci] = nb
                self.block_tables[slot, ci] = nb
                self._deref_block(b)
                _PREFIX_COW.inc()
        return True

    def truncate_blocks(self, slot, keep):
        """Roll the slot's table back to its first ``keep`` blocks (the
        speculative reject path: rejected draft rows' freshly-grown
        blocks return to the pool — private blocks to the free list,
        registered ones park reclaimable). Rows already written into
        KEPT blocks past ``seq_lens`` need no scrub: every reader masks
        by seq_len and the next append overwrites them."""
        blocks = self._slot_blocks[slot]
        if keep >= len(blocks):
            return
        for b in reversed(blocks[keep:]):
            self._deref_block(b)
        del blocks[keep:]
        self.block_tables[slot, keep:] = 0

    def free_slot(self, slot):
        for b in reversed(self._slot_blocks[slot]):
            self._deref_block(b)
        self._slot_blocks[slot] = []
        self._live[slot] = False
        self.block_tables[slot] = 0
        self.seq_lens[slot] = 0

    # -- prefix cache ------------------------------------------------------

    def plan_prefix(self, token_ids):
        """Match a prompt against the prefix index (pure — no side
        effects): longest run of leading full chunks whose rolling
        digests are resident, optionally extended by a partially-filled
        tail block whose registered tokens prefix-match the remainder.
        The partial block is mapped read-only when it exactly completes
        the prompt (``partial_shared``), else it must be copied at
        admission (writes would land mid-block — the "divergence /
        extension inside a shared block" COW case)."""
        ids = np.asarray(token_ids).reshape(-1)
        n = int(ids.size)
        bs = self.block_size
        if self.state_spec is not None:
            # the no-hit plan: a prefix's K and V blocks could be mapped,
            # but the recurrent state at its end exists nowhere
            return PrefixPlan(
                ids=ids, num_tokens=n,
                chunks_total=max(1, math.ceil(n / bs)), digests=[],
                matched_full=0, matched_blocks=[], partial_block=None,
                partial_len=0, partial_shared=False, covered_tokens=0)
        digests = chunk_digests(ids, bs)
        matched, blocks = 0, []
        for d in digests:
            b = self._prefix_index.get(d)
            if b is None:
                break
            blocks.append(b)
            matched += 1
        covered = matched * bs
        partial_block, partial_len, partial_shared = None, 0, False
        if covered < n:
            # at the first uncovered chunk (divergence point or true
            # tail), a registered partially-filled block whose tokens
            # prefix-match the remainder still saves compute: mapped
            # read-only when it exactly completes the prompt, copied
            # (COW) when this prompt writes past its matched tokens
            parent = digests[matched - 1] if matched else b""
            rem = n - covered
            for p in range(min(bs - 1, rem), 0, -1):
                b = self._partial_index.get(
                    _partial_key(parent, ids[covered:covered + p]))
                if b is not None:
                    partial_block, partial_len = b, p
                    partial_shared = (p == rem)
                    covered += p
                    break
        return PrefixPlan(
            ids=ids, num_tokens=n,
            chunks_total=max(1, math.ceil(n / bs)),
            digests=digests, matched_full=matched,
            matched_blocks=blocks, partial_block=partial_block,
            partial_len=partial_len, partial_shared=partial_shared,
            covered_tokens=covered)

    def alloc_slot_cached(self, plan):
        """Claim a slot for a planned prompt: matched blocks are mapped
        read-only (refcount++), a matched-but-extended partial block is
        copied (COW), and only the uncovered chunks allocate fresh
        blocks. Returns the slot id or None (no slot / not enough
        reclaimable blocks — the plan is untouched on failure)."""
        slot = self._choose_slot()
        if slot is None or plan.chunks_total > self.max_blocks_per_seq:
            return None
        sl = self.slice_of_slot(slot) if self.num_slices > 1 else None
        shared = list(plan.matched_blocks)
        cow_src = None
        if plan.partial_block is not None:
            if plan.partial_shared:
                shared.append(plan.partial_block)
            else:
                cow_src = plan.partial_block
        # pin everything we read before any eviction can run (matched
        # blocks may live in ANY slice — prefix sharing crosses slice
        # boundaries read-only; only FRESH blocks bind to the slot's
        # slice)
        for b in shared:
            self._ref_block(b)
        if cow_src is not None:
            self._ref_block(cow_src)
        fresh_needed = plan.chunks_total - len(shared)
        if fresh_needed > self.num_free_blocks(sl):
            if cow_src is not None:
                self._deref_block(cow_src)
            for b in reversed(shared):
                self._deref_block(b)
            return None
        fresh = [self._take_block(sl) for _ in range(fresh_needed)]
        if cow_src is not None:
            self._copy_block_rows(cow_src, fresh[0])
            self._deref_block(cow_src)
            _PREFIX_COW.inc()
        blocks = shared + fresh
        self._slot_blocks[slot] = blocks
        self._live[slot] = True
        row = np.zeros((self.max_blocks_per_seq,), np.int32)
        row[:len(blocks)] = blocks
        self.block_tables[slot] = row
        self.seq_lens[slot] = 0
        self.state_fresh[slot] = True
        # a COW-extended partial match counts as a HIT (its registered
        # tokens were served from cache even though the block itself is
        # a fresh copy) — keeps these counters consistent with the
        # serving.prefill span's hit_blocks attr (= plan.hit_blocks)
        hit = plan.hit_blocks
        _PREFIX_HITS.inc(hit)
        _PREFIX_MISSES.inc(plan.chunks_total - hit)
        return slot

    def commit_prefix(self, slot, plan):
        """Register the freshly-prefilled chunks of this slot in the
        prefix index (after the prefill wrote them — their rows are
        immutable from here on: appends only ever touch rows past the
        registered token count, and shared writes COW first). First
        registration wins; an already-indexed digest keeps its block. A
        cache that holds recurrent state registers nothing
        (``plan_prefix``)."""
        if self.state_spec is not None:
            return
        blocks = self._slot_blocks[slot]
        for i in range(plan.matched_full, len(plan.digests)):
            d = plan.digests[i]
            if d in self._prefix_index:
                continue
            b = blocks[i]
            self._prefix_index[d] = b
            self._block_keys.setdefault(b, []).append(("full", d))
        rem = plan.num_tokens - len(plan.digests) * self.block_size
        if rem > 0 and not plan.partial_shared:
            parent = plan.digests[-1] if plan.digests else b""
            key = _partial_key(parent, plan.ids[plan.num_tokens - rem:])
            if key not in self._partial_index:
                b = blocks[len(plan.digests)]
                self._partial_index[key] = b
                self._block_keys.setdefault(b, []).append(("part", key))


# ---------------------------------------------------------------------------
# device-side functional ops (static shapes, jit-safe)
# ---------------------------------------------------------------------------

def _block_copy(pools, dst, src):
    rows = src if isinstance(src, list) else [p[src] for p in pools]
    return [p.at[dst].set(r) for p, r in zip(pools, rows)]


_block_copy.__name__ = _block_copy.__qualname__ = "kv_block_copy"
# the program behind ``PagedKVCache.write_blocks``: ``src`` block ids
# gather their rows from the pools themselves, a list holds the rows
_kv_block_copy = jax.jit(_block_copy, donate_argnums=(0,))


def _pools_and_rows(k_pool, v_pool, k_new, v_new, k_scale, v_scale):
    """What a write sets, and with what: a full-precision cache's two
    pools take the rows as they are; an int8 cache (its scale arrays
    passed) takes them quantized per (row, kv-head) by the absmax
    formula (``quantization.quantize_rows``), and its scale arrays take
    the scales — THE quantization point of the int8 KV tier."""
    if k_scale is None:
        return (k_pool, v_pool), (k_new, v_new)
    from ..quantization import quantize_rows
    kq, ks = quantize_rows(k_new)
    vq, vs = quantize_rows(v_new)
    return (k_pool, v_pool, k_scale, v_scale), (kq, vq, ks, vs)


def paged_prefill_write(k_pool, v_pool, block_row, k_new, v_new,
                        k_scale=None, v_scale=None):
    """Write a prompt's KV [S, Hk, D] into the pool blocks listed in
    `block_row` [max_blocks_per_seq], whole pages at a time. S is padded
    to a block multiple by the caller. Functional: returns the pools
    (then an int8 cache's scale arrays, when passed) with the rows set —
    a serving program that was handed them donated writes them in
    place; called eagerly (the tests' reference) each result is a new
    array."""
    bs = k_pool.shape[1]
    nb = k_new.shape[0] // bs
    pools, rows = _pools_and_rows(k_pool, v_pool, k_new, v_new, k_scale,
                                  v_scale)
    pages = [r.reshape(nb, bs, *r.shape[1:]).astype(p.dtype)
             for p, r in zip(pools, rows)]
    blocks = block_row[:nb]
    return tuple(p.at[blocks].set(pg) for p, pg in zip(pools, pages))


def _put_rows(pool, new, blocks, offs, valid):
    """The masked row scatter behind every row-by-row write: row ``i``
    of ``new`` [N, ...] lands at ``pool[blocks[i], offs[i]]`` where
    ``valid[i]``; the caller pointed every other row at the null block's
    row 0, which keeps what it holds."""
    keep = valid[(slice(None),) + (None,) * (new.ndim - 1)]
    return pool.at[blocks, offs].set(
        jnp.where(keep, new.astype(pool.dtype), pool[blocks, offs]))


def _write_rows(k_pool, v_pool, blocks, offs, valid, k_new, v_new,
                k_scale, v_scale):
    """:func:`_put_rows` of ``k_new``/``v_new`` [N, Hk, D] into the two
    pools. Returns the pools, then the scale arrays of an int8 cache."""
    pools, rows = _pools_and_rows(k_pool, v_pool, k_new, v_new, k_scale,
                                  v_scale)
    return tuple(_put_rows(p, r, blocks, offs, valid)
                 for p, r in zip(pools, rows))


def _tail_targets(bs, block_row, n, start, write_start, total_len):
    """(blocks, offs, valid) of the ``n`` positions from ``start``: those
    in ``[write_start, total_len)`` land in the slot's pages, the rest
    (shared prefix rows, bucket padding) are pointed at the null block."""
    pos = start + jnp.arange(n, dtype=jnp.int32)
    valid = (pos >= write_start) & (pos < total_len)
    b_idx = jnp.where(valid, pos // bs, 0)
    blocks = jnp.where(valid, block_row[b_idx], 0)
    offs = jnp.where(valid, pos % bs, 0)
    return blocks, offs, valid


def paged_prefill_write_masked(k_pool, v_pool, block_row, k_new, v_new,
                               start, write_start, total_len,
                               k_scale=None, v_scale=None):
    """Write a prefill TAIL's KV into the pool: ``k_new``/``v_new``
    [S, Hk, D] hold positions ``start .. start+S-1``; only positions in
    ``[write_start, total_len)`` actually land (shared prefix rows and
    bucket padding are masked to the null block 0 — padding must never
    poison cached content). All operands static-shaped; start/
    write_start/total_len are traced scalars. An int8 cache passes its
    scale arrays and gets them back behind the pools."""
    blocks, offs, valid = _tail_targets(
        k_pool.shape[1], block_row, k_new.shape[0], start, write_start,
        total_len)
    return _write_rows(k_pool, v_pool, blocks, offs, valid, k_new, v_new,
                       k_scale, v_scale)


def latent_prefill_write_masked(row_pool, block_row, rows, start,
                                write_start, total_len):
    """:func:`paged_prefill_write_masked` for a latent cache's one pool
    a layer: ``rows`` [S, 1, lanes], row by row. Returns the pool."""
    return _put_rows(row_pool, rows, *_tail_targets(
        row_pool.shape[1], block_row, rows.shape[0], start, write_start,
        total_len))


def _gather_kv(pool, index, scale, dtype):
    """Pool gather for the dense attention paths: full-precision pools
    gather as-is; quantized pools (``scale`` not None) dequantize the
    gathered rows into the compute ``dtype`` — THE dequant point of
    the int8 KV tier (XLA fuses it into the attention that follows,
    so no dequantized pool ever materializes in HBM)."""
    g = pool[index]
    if scale is None:
        return g
    from ..quantization import dequantize_rows
    return dequantize_rows(g, scale[index], dtype)


def paged_prefix_attention_dense(q, k_pool, v_pool, block_row, q_start,
                                 total_len, scale=None, k_scale=None,
                                 v_scale=None, block_len=1):
    """Chunked-prefill attention for the prefix-cache tail: queries
    [S, Hq, D] sit at absolute positions ``q_start .. q_start+S-1`` and
    attend the slot's whole paged context (cached prefix blocks + the
    tail KV just written), causal by absolute position and masked to
    ``total_len``. Same gather + group-folded GQA formulation as
    `paged_decode_attention_dense`, generalized to S queries; padded
    query rows produce junk that the caller never reads. ``block_len``
    L > 1 makes the mask block-causal: key j is visible to query i iff
    ``j // L <= i // L`` (blocks aligned to position 0)."""
    s, hq, d = q.shape
    _, bs, hk, _ = k_pool.shape
    g = hq // hk
    s_max = block_row.shape[0] * bs

    k = _gather_kv(k_pool, block_row, k_scale, q.dtype).reshape(
        s_max, hk, d)
    v = _gather_kv(v_pool, block_row, v_scale, q.dtype).reshape(
        s_max, hk, d)

    sm_scale = jnp.float32(scale if scale is not None
                           else 1.0 / math.sqrt(d))
    qg = q.reshape(s, hk, g, d)
    logits = jnp.einsum("sngd,tnd->sngt", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    pos_q = q_start + jnp.arange(s, dtype=jnp.int32)
    pos_k = jnp.arange(s_max, dtype=jnp.int32)
    if block_len > 1:
        pos_q, pos_k = pos_q // block_len, pos_k // block_len
    mask = (pos_k[None, :] <= pos_q[:, None]) & \
        (jnp.arange(s_max, dtype=jnp.int32)[None, :] < total_len)
    logits = jnp.where(mask[:, None, None, :], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(mask[:, None, None, :], probs, 0.0)
    out = jnp.einsum("sngt,tnd->sngd", probs.astype(v.dtype), v)
    return out.reshape(s, hq, d).astype(q.dtype)


def _step_targets(bs, block_tables, positions, active):
    """(blocks, offs) of one token a slot at ``positions``: an inactive
    slot's is pointed at the null block's row 0."""
    b_idx = positions // bs
    offs = positions % bs
    rows = jnp.arange(block_tables.shape[0], dtype=jnp.int32)
    blocks = jnp.where(active, block_tables[rows, b_idx], 0)
    return blocks, jnp.where(active, offs, 0)


def paged_decode_write(k_pool, v_pool, block_tables, positions, k_new,
                       v_new, active, k_scale=None, v_scale=None):
    """Scatter one new token's KV per slot: k_new/v_new [B, Hk, D] at
    `positions` [B] (the token's index). Inactive slots write to the null
    block 0 slot 0 — harmless, masked everywhere. An int8 cache passes
    its scale arrays and gets them back behind the pools."""
    blocks, offs = _step_targets(k_pool.shape[1], block_tables, positions,
                                 active)
    return _write_rows(k_pool, v_pool, blocks, offs, active, k_new, v_new,
                       k_scale, v_scale)


def latent_decode_write(row_pool, block_tables, positions, rows, active):
    """:func:`paged_decode_write` for a latent cache's one pool a layer:
    ``rows`` [B, 1, lanes], one token a slot. Returns the pool."""
    return _put_rows(row_pool, rows, *_step_targets(
        row_pool.shape[1], block_tables, positions, active), active)


def paged_decode_attention(q, k_pool, v_pool, block_tables, seq_lens,
                           scale=None, k_scale=None, v_scale=None,
                           kernel_mode=None, kernel_name="paged_decode"):
    """Masked decode attention over the paged cache — THE kernel
    routing point (docs/PERF.md "Pallas serving-kernel tier").

    q [B, Hq, D] (one query token per slot); returns [B, Hq, D].
    Routing (``kernel_mode``: the engine's construction-resolved
    ``FLAGS_paged_kernel``): ``auto`` takes the fused Pallas kernel on TPU —
    full-precision AND int8 pools (the kernel carries the scale rows
    and dequantizes in VMEM), the chunked flash-decode variant past
    ``_CHUNK_MIN_PAGES`` — and the dense XLA reference below on CPU;
    ``pallas`` forces the kernel everywhere (interpret mode on CPU,
    tier-1 testable); ``dense`` forces the reference byte-for-byte
    with serving.kernel.* counter silence. The pallas/dense/interpret
    route counters move at the routing decision
    (tools/kernel_gate.py pins movement and silence). ``kernel_name``
    is the Pallas call's name in a device trace.
    """
    mode = resolve_paged_kernel(kernel_mode)
    if mode == "dense" or (k_scale is None) != (v_scale is None):
        # forced dense: the pre-kernel path, byte-for-byte, before any
        # counter moves (mismatched scales never happens from engines;
        # route it dense so the reference raises the shape error)
        return paged_decode_attention_dense(
            q, k_pool, v_pool, block_tables, seq_lens, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    route = kernel_route(mode)
    if route == "dense":
        _KERN_DENSE.inc()
        return paged_decode_attention_dense(
            q, k_pool, v_pool, block_tables, seq_lens, scale=scale,
            k_scale=k_scale, v_scale=v_scale)
    _KERN_PALLAS.inc()
    if route == "interpret":
        _KERN_INTERPRET.inc()
    from ..kernels.pallas.paged_attention import (
        paged_decode_attention_chunked, paged_decode_attention_kernel)
    if block_tables.shape[1] >= _CHUNK_MIN_PAGES:
        return paged_decode_attention_chunked(
            q, k_pool, v_pool, block_tables, seq_lens, scale=scale,
            k_scale=k_scale, v_scale=v_scale, name=kernel_name)
    return paged_decode_attention_kernel(
        q, k_pool, v_pool, block_tables, seq_lens, scale=scale,
        k_scale=k_scale, v_scale=v_scale, name=kernel_name)


def paged_block_attention(q, k_pool, v_pool, block_tables, seq_lens,
                          scale=None, kernel_mode=None):
    """Attention of a block of L query rows a slot over the paged cache,
    with no mask inside the block (block-diffusion decoding): q
    [B, L, Hq, D]; every row of slot ``b`` sees its first ``seq_lens[b]``
    keys, the block's own L among them (the caller wrote them and passes
    ``len + L``). The L rows join the GQA group (``fold_block_rows``),
    so the route, the kernels and the dense reference are
    :func:`paged_decode_attention`'s; the Pallas call is named
    ``paged_block*``. L = 1 is that function, program for program."""
    from ..kernels.pallas.paged_attention import (fold_block_rows,
                                                   unfold_block_rows)
    l, hk = q.shape[1], k_pool.shape[2]
    out = paged_decode_attention(
        fold_block_rows(q, hk), k_pool, v_pool, block_tables, seq_lens,
        scale=scale, kernel_mode=kernel_mode,
        kernel_name="paged_decode" if l == 1 else "paged_block")
    return unfold_block_rows(out, l, hk)


def paged_decode_attention_dense(q, k_pool, v_pool, block_tables, seq_lens,
                                 scale=None, k_scale=None, v_scale=None):
    """Dense XLA reference for `paged_decode_attention`: gathers each
    slot's blocks (materializing [B, S_max, Hk, D]; quantized pools
    dequantize in the gather), masks positions >= seq_len, GQA
    group-folded (no KV expansion)."""
    b, hq, d = q.shape
    nb_pool, bs, hk, _ = k_pool.shape
    g = hq // hk
    s_max = block_tables.shape[1] * bs

    k = _gather_kv(k_pool, block_tables, k_scale, q.dtype)
    v = _gather_kv(v_pool, block_tables, v_scale, q.dtype)
    k = k.reshape(b, s_max, hk, d)
    v = v.reshape(b, s_max, hk, d)

    sm_scale = jnp.float32(scale if scale is not None
                           else 1.0 / math.sqrt(d))
    qg = q.reshape(b, hk, g, d)
    logits = jnp.einsum("bngd,btnd->bngt", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    pos = jnp.arange(s_max, dtype=jnp.int32)
    mask = pos[None, :] < seq_lens[:, None]  # [B, s_max]
    logits = jnp.where(mask[:, None, None, :], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked (inactive) slots: softmax of all -1e30 is uniform junk;
    # zero it so output is exactly 0
    probs = jnp.where(mask[:, None, None, :], probs, 0.0)
    out = jnp.einsum("bngt,btnd->bngd", probs.astype(v.dtype), v)
    return out.reshape(b, hq, d).astype(q.dtype)


def paged_decode_attention_tp(q, k_pool, v_pool, block_tables, seq_lens,
                              mesh, scale=None, k_scale=None,
                              v_scale=None, kernel_mode=None):
    """Tensor-parallel decode attention under an explicit
    ``jax.shard_map`` (docs/SERVING.md "Mesh-sharded serving"): the
    kv-head axis of the pools and the q-head axis of the queries split
    along the mesh's ``model`` axis, and each shard runs the plain
    :func:`paged_decode_attention` on its LOCAL heads — gathering only
    its own pool shard and routing the Pallas kernel
    (kernels/pallas/paged_attention.py) per shard on TPU. Attention is
    embarrassingly parallel over heads (GQA groups never cross a
    kv-head), so the body needs NO collective; the all_gather /
    psum_scatter pair lives at the o_proj boundary, where GSPMD puts
    it. Called whenever the mesh's model axis splits the heads
    (``ServingMesh.shard_map_armed``)."""
    from jax.sharding import PartitionSpec as P

    jm = mesh.jax_mesh
    head = P(None, "model", None)
    pool = P(None, None, "model", None)
    rep = P()

    if k_scale is not None:
        srow = P(None, None, "model")

        def local(qq, kp, vp, ksc, vsc, tbl, lens):
            return paged_decode_attention(qq, kp, vp, tbl, lens,
                                          scale=scale, k_scale=ksc,
                                          v_scale=vsc,
                                          kernel_mode=kernel_mode)

        f = jax.shard_map(local, mesh=jm,
                          in_specs=(head, pool, pool, srow, srow,
                                    rep, rep),
                          out_specs=head, check_vma=False)
        return f(q, k_pool, v_pool, k_scale, v_scale, block_tables,
                 seq_lens)

    def local(qq, kp, vp, tbl, lens):
        return paged_decode_attention(qq, kp, vp, tbl, lens, scale=scale,
                                      kernel_mode=kernel_mode)

    f = jax.shard_map(local, mesh=jm,
                      in_specs=(head, pool, pool, rep, rep),
                      out_specs=head, check_vma=False)
    return f(q, k_pool, v_pool, block_tables, seq_lens)


# ---------------------------------------------------------------------------
# speculative multi-position sweep (docs/SERVING.md "Decode speed tiers")
# ---------------------------------------------------------------------------

def paged_spec_write(k_pool, v_pool, block_tables, start_lens, k_new,
                     v_new, n_inputs, active, k_scale=None, v_scale=None):
    """Scatter S candidate tokens' KV per slot for the speculative
    verify sweep: ``k_new``/``v_new`` [B, S, Hk, D] land at absolute
    positions ``start_lens[b] + i``. Only the first ``n_inputs[b]``
    positions of an active slot are real — the rest (draft padding,
    inactive slots) are masked to the reserved null block 0, the
    bucketing convention. Quantized pools (scales passed) quantize
    per row on the way in. Returns the pools (+ scales) with the rows
    set, in place inside a program that took them donated."""
    b, s = k_new.shape[:2]
    bs = k_pool.shape[1]
    pos = start_lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    valid = active[:, None] & \
        (jnp.arange(s, dtype=jnp.int32)[None, :] < n_inputs[:, None])
    b_idx = jnp.where(valid, pos // bs, 0)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    blocks = jnp.where(valid, block_tables[rows, b_idx], 0)
    offs = jnp.where(valid, pos % bs, 0)
    return _write_rows(
        k_pool, v_pool, blocks.reshape(-1), offs.reshape(-1),
        valid.reshape(-1), k_new.reshape(b * s, *k_new.shape[2:]),
        v_new.reshape(b * s, *v_new.shape[2:]), k_scale, v_scale)


def paged_spec_attention_dense(q, k_pool, v_pool, block_tables,
                               start_lens, active, scale=None,
                               k_scale=None, v_scale=None):
    """Batched multi-position attention for the speculative verify
    sweep: queries [B, S, Hq, D] sit at absolute positions
    ``start_lens[b] + i`` and attend each slot's whole paged context
    causally by absolute position — query i sees exactly the keys a
    sequential decode step at that position would (pos_k <= pos_q), so
    greedy acceptance is bit-equivalent to stepping one token at a
    time. The S=1 case degenerates to `paged_decode_attention_dense`'s
    formulation. Inactive slots are fully masked (junk-free zeros);
    padded draft rows produce junk the host never reads."""
    b, s, hq, d = q.shape
    _, bs, hk, _ = k_pool.shape
    g = hq // hk
    s_max = block_tables.shape[1] * bs

    k = _gather_kv(k_pool, block_tables, k_scale, q.dtype).reshape(
        b, s_max, hk, d)
    v = _gather_kv(v_pool, block_tables, v_scale, q.dtype).reshape(
        b, s_max, hk, d)

    sm_scale = jnp.float32(scale if scale is not None
                           else 1.0 / math.sqrt(d))
    qg = q.reshape(b, s, hk, g, d)
    logits = jnp.einsum("bsngd,btnd->bsngt", qg, k,
                        preferred_element_type=jnp.float32) * sm_scale
    pos_q = start_lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    pos_k = jnp.arange(s_max, dtype=jnp.int32)
    mask = (pos_k[None, None, :] <= pos_q[:, :, None]) & \
        active[:, None, None]
    logits = jnp.where(mask[:, :, None, None, :], logits,
                       jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(mask[:, :, None, None, :], probs, 0.0)
    out = jnp.einsum("bsngt,btnd->bsngd", probs.astype(v.dtype), v)
    return out.reshape(b, s, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# continuous batching engine
# ---------------------------------------------------------------------------

def validate_request(prompt_ids, max_new_tokens, max_seq_len, cache,
                     who="add_request"):
    """Shared submit-time validation for the base engine AND the serving
    scheduler (one place, so the contracts cannot drift): non-empty
    prompt, >= 1 new token, prompt and prompt+max_new within
    ``max_seq_len``, and the worst-case block demand
    ``ceil((prompt+max_new-1)/block_size)`` within the pool — a request
    that could never finish even alone must be rejected HERE, not hang
    admission forever. Returns the flattened prompt array."""
    prompt = np.asarray(prompt_ids).reshape(-1)
    if prompt.size == 0:
        raise ValueError(f"{who}: empty prompt")
    if max_new_tokens < 1:
        raise ValueError(f"{who}: max_new_tokens must be >= 1, got "
                         f"{max_new_tokens}")
    if prompt.size > max_seq_len:
        raise ValueError(
            f"{who}: prompt length {prompt.size} exceeds max_seq_len "
            f"{max_seq_len}")
    if prompt.size + max_new_tokens > max_seq_len:
        raise ValueError(
            f"{who}: prompt ({prompt.size}) + max_new_tokens "
            f"({max_new_tokens}) exceeds max_seq_len {max_seq_len}")
    need = math.ceil((prompt.size + max_new_tokens - 1) / cache.block_size)
    usable = cache.num_blocks - 1
    if need > usable:
        raise ValueError(
            f"{who}: request needs up to {need} KV blocks but the pool "
            f"has only {usable} usable; increase num_blocks or lower "
            "max_new_tokens")
    return prompt

def sized_num_blocks(num_blocks, max_batch, max_blocks_per_seq, kv_dtype,
                     head_dim, dtype):
    """Default pool sizing shared by both engines: the classic
    ``max_batch * max_blocks_per_seq`` (+1 reserved null) block budget
    at full precision; int8 storage fits :func:`quant_block_ratio`
    times as many blocks in the SAME HBM bytes — the capacity
    multiplier the quantized tier exists for (``occupancy()`` reports
    it, ``pool_bytes()`` stays ~flat). An explicit ``num_blocks``
    always wins."""
    if num_blocks is not None:
        return num_blocks
    base = max_batch * max_blocks_per_seq
    if kv_dtype == "int8":
        base = int(base * quant_block_ratio(head_dim, dtype))
    return base + 1


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    generated: list = field(default_factory=list)
    slot: int = -1


class ContinuousBatchingEngine:
    """Slot-based continuous batching over a paged cache.

    add_request() enqueues prompts; step() admits waiting prompts into
    free slots (prefill) and decodes ONE token for every live slot (a
    single jitted program whose shapes never change); finished sequences
    release their blocks immediately.
    """

    def __init__(self, model, *, max_batch=8, block_size=16,
                 max_seq_len=2048, num_blocks=None, temperature=0.0,
                 eos_token_id=None, dtype=jnp.bfloat16,
                 kv_cache_dtype=None):
        cfg = model.config
        self.model = model
        self.eos_token_id = eos_token_id
        self.temperature = temperature
        self.max_seq_len = max_seq_len
        mbps = math.ceil(max_seq_len / block_size)
        # int8 KV storage (read ONCE at construction, like the serving
        # scheduler's flag-resolved kwargs): default pool sizing grows
        # by the honest byte ratio, so the same HBM budget serves ~2x
        # the sequences
        if kv_cache_dtype is None:
            from ..core import flags as _flags
            kv_cache_dtype = _flags.flag("FLAGS_kv_cache_dtype")
        kv_dtype = resolve_kv_dtype(kv_cache_dtype)
        hd = cfg.head_dim
        num_blocks = sized_num_blocks(
            num_blocks, max_batch, mbps, kv_dtype, hd, dtype)
        self.cache = PagedKVCache(
            getattr(model, "kv_cache_layers", cfg.num_layers),
            cfg.num_kv_heads, hd, num_blocks=num_blocks,
            block_size=block_size, max_blocks_per_seq=mbps,
            max_batch=max_batch, dtype=dtype, kv_dtype=kv_dtype,
            recurrent_state=getattr(model, "recurrent_state", None),
            latent_rows=getattr(model, "latent_rows", None))
        self.waiting: list[_Request] = []
        self.running: dict[int, _Request] = {}  # slot -> request
        self.finished: dict[int, _Request] = {}
        self._next_rid = 0
        self._last_tok = np.zeros((max_batch,), np.int64)
        self._remaining = np.zeros((max_batch,), np.int64)

    def add_request(self, prompt_ids, max_new_tokens=32):
        prompt = validate_request(prompt_ids, max_new_tokens,
                                  self.max_seq_len, self.cache)
        rid = self._next_rid
        self._next_rid += 1
        self.waiting.append(_Request(rid, prompt, max_new_tokens))
        return rid

    @property
    def has_work(self):
        return bool(self.waiting or self.running)

    def _prefill_ids(self, req):
        """Prompt plus any already-generated tokens: after a preemption
        the request re-prefills its full context, and the prefill's
        sampled token is the NEXT new token (greedy decode therefore
        continues bit-identically to an uncontended run)."""
        if not req.generated:
            return req.prompt
        return np.concatenate(
            [req.prompt,
             np.asarray(req.generated, dtype=req.prompt.dtype)])

    def _admit(self):
        admitted = []
        still_waiting = []
        for req in self.waiting:
            slot = self.cache.alloc_slot(
                len(req.prompt) + len(req.generated)) \
                if len(self.running) < self.cache.max_batch else None
            if slot is None:
                still_waiting.append(req)
                continue
            req.slot = slot
            self.running[slot] = req
            admitted.append(req)
        self.waiting = still_waiting
        for req in admitted:
            tok = self.model.paged_prefill(self.cache, req.slot,
                                           self._prefill_ids(req),
                                           temperature=self.temperature)
            self._last_tok[req.slot] = tok
            self._remaining[req.slot] = \
                req.max_new_tokens - len(req.generated) - 1
            req.generated.append(int(tok))
            self._maybe_finish(req.slot)

    def _preempt(self, slot):
        """Victim loses its slot and blocks NOW; its generated tokens are
        kept and it rejoins the FRONT of the waiting queue, where the
        next `_admit` re-prefills prompt+generated (see `_prefill_ids`)."""
        req = self.running.pop(slot)
        self.cache.free_slot(slot)
        req.slot = -1
        self.waiting.insert(0, req)
        _PREEMPTS.inc()

    def _maybe_finish(self, slot):
        req = self.running.get(slot)
        if req is None:
            return
        done = self._remaining[slot] <= 0 or (
            self.eos_token_id is not None
            and req.generated and req.generated[-1] == self.eos_token_id)
        if done:
            self.cache.free_slot(slot)
            del self.running[slot]
            self.finished[req.rid] = req

    def step(self):
        """Admit waiting prompts, then decode one token for all live
        slots. Returns list of (rid, token) produced this step."""
        self._admit()
        if not self.running:
            return []
        active_np = np.zeros((self.cache.max_batch,), bool)
        for slot in self.running:
            active_np[slot] = True
        # grow tables where the next token crosses a block boundary
        # (seq_lens is host metadata: no device fetch here)
        lens = self.cache.seq_lens
        for slot in list(self.running):
            denied = self.cache.ensure_capacity(slot, int(lens[slot]) + 1)
            if not denied:
                req = self.running[slot]
                if denied.reason == CapacityError.SEQ_LIMIT:
                    # no amount of freeing helps — the sequence itself
                    # outgrew the table (validate_request bounds this,
                    # so only a caller bypassing it can get here)
                    raise RuntimeError(
                        f"request {req.rid} outgrew max_blocks_per_seq: "
                        f"{denied.detail}")
                # pool exhausted: preempt (free the blocks, requeue for
                # re-prefill once others release pages) instead of
                # silently truncating the sequence
                if len(self.running) == 1:
                    raise RuntimeError(
                        f"KV pool exhausted: request {req.rid} needs "
                        f"{math.ceil((int(lens[slot]) + 1) / self.cache.block_size)} "
                        f"blocks but the pool has only "
                        f"{self.cache.num_blocks - 1} usable and no other "
                        "running request to wait for; increase num_blocks")
                self._preempt(slot)
                active_np[slot] = False
        if not self.running:
            return []
        toks = self.model.paged_decode_step(
            self.cache, np.asarray(self._last_tok), active_np,
            temperature=self.temperature)
        toks_np = np.asarray(toks)
        out = []
        for slot, req in list(self.running.items()):
            t = int(toks_np[slot])
            req.generated.append(t)
            self._last_tok[slot] = t
            self._remaining[slot] -= 1
            out.append((req.rid, t))
            self._maybe_finish(slot)
        return out

    def run_to_completion(self):
        """Drain all requests; returns {rid: generated token list}."""
        while self.has_work:
            self.step()
        return {rid: req.generated for rid, req in self.finished.items()}
