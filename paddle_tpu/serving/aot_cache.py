"""Persistent AOT compile cache: zero-cold-start process boots.

Warm-path serving is zero-recompile (serving/bucketing.py bounds the
program set; tools/serving_gate.py pins it) — but every FRESH process
still pays full XLA compilation per prefill bucket + the decode step,
which is fatal for rolling deploys and elastic scale-out: a replica
joining the fleet burns seconds of compile before its first token.
This module makes compilation a one-time fleet cost instead of a
per-process cost:

- the serving-path jit entry points (``Llama.paged_prefill`` /
  ``paged_prefill_extend`` / ``paged_decode_step``, and the
  deferred-chain programs under the ``passes/v1|v2`` / verbatim
  namespaces in ``core/deferred.py``) are wrapped in
  :class:`AOTFunction`, which dispatches per argument signature and —
  instead of letting ``jax.jit`` trace+compile on first call — runs
  ``jitted.lower(*args)`` (a pure python trace, no XLA), fingerprints
  the lowered module, and either **loads** a serialized executable
  from the on-disk store (``jax.experimental.serialize_executable``,
  zero backend compiles) or **compiles and stores** it for the next
  process;
- the **fingerprint** is git-sha-independent and content-addressed:
  blake2b over the jax version, the backend signature
  (platform/device-kind/device-count), the compilation-relevant jax
  config (x64, default matmul precision), a caller tag, and the full
  lowered StableHLO text — which itself encodes the jaxpr, every
  aval, and every flag that changed the traced program (the fusion /
  passes flags produce different HLO, hence different entries). Two
  processes that would compile the same program hash to the same
  entry; anything else misses;
- entries follow the **checkpoint-v2 durability discipline**
  (distributed/checkpoint.py): payloads are crc32-guarded, written to
  a private ``.tmp.<pid>`` staging file, fsynced, and
  ``os.replace``d into place — a crashed writer leaves no torn entry.
  A corrupt/truncated/foreign entry **quarantines** to
  ``*.corrupt-N`` (counted ``jit.aot.quarantined``, degraded
  ``resilience.degrade.aot_cache.corrupt``) and falls back to a
  normal compile that re-stores a fresh entry — a wrong executable is
  never loaded, and the failure mode is "pay the compile", never
  "serve garbage".

Telemetry rides the always-on registry: ``jit.aot.{hits,misses,
stores,quarantined}`` counters, ``jit.aot.bytes`` (payload bytes
moved), ``jit.aot.load_us`` (deserialize latency), and
``jit.aot.saved_us`` — the compile seconds each hit did NOT pay,
read back from the entry's recorded compile time. A thread-local
mirror (:func:`thread_saved_seconds`, the ``metrics.
thread_compile_seconds`` pattern) lets the serving scheduler bill
per-request compile-seconds-saved into PR 9's cost attribution
(``CostReport.aot_saved_us``) without touching the closure property.
``profiler.summary()`` renders the family as the "Cold start" view.

Arming: ``FLAGS_serving_aot_cache`` (default on) AND a non-empty
``FLAGS_aot_cache_dir`` (or ``PADDLE_TPU_AOT_CACHE`` env). Disarmed,
:class:`AOTFunction` forwards straight to the wrapped ``jax.jit``
callable — byte-for-byte the pre-cache behavior with every
``jit.aot.*`` counter silent (tools/router_gate.py pins it).

Fault sites (testing/faults.py; catalog in docs/ROBUSTNESS.md):
``aot.load`` fires before a store read (an injected failure falls
back to a normal compile — degraded, never fatal), ``aot.store``
before a store write (serving keeps the compiled program in hand).
"""

from __future__ import annotations

import hashlib
import os
import pickle
import struct
import threading
import time
import zlib

from ..core import flags as flags_mod
from ..core import resilience
from ..profiler import metrics as _metrics
from ..testing import faults as _faults

__all__ = ["AOTFunction", "wrap", "armed", "cache_dir", "configure",
           "fingerprint", "thread_saved_seconds", "entry_path",
           "FORMAT", "MAGIC"]

MAGIC = b"PTPUAOT1"
FORMAT = 1
# MAGIC(8) + crc32(4) + payload length(8)
_HEADER = struct.Struct(">4sQ")

_c_hits = _metrics.counter("jit.aot.hits")
_c_misses = _metrics.counter("jit.aot.misses")
_c_stores = _metrics.counter("jit.aot.stores")
_c_quarantined = _metrics.counter("jit.aot.quarantined")
_c_bytes = _metrics.counter("jit.aot.bytes")
_c_saved_us = _metrics.counter("jit.aot.saved_us")
_h_load_us = _metrics.histogram(
    "jit.aot.load_us",
    bounds=(100, 500, 1000, 5000, 10000, 50000, 100000, 500000))

# compile seconds NOT paid by this thread thanks to cache hits — the
# per-thread delta discipline of metrics.thread_compile_seconds, so the
# scheduler can bill savings to the exact request whose dispatch hit
_tls = threading.local()


def thread_saved_seconds():
    """Cumulative compile seconds saved by AOT hits on the calling
    thread (0.0 before any hit)."""
    return getattr(_tls, "saved", 0.0)


def _note_saved(compile_s):
    _tls.saved = getattr(_tls, "saved", 0.0) + compile_s
    _c_saved_us.inc(compile_s * 1e6)


# -- arming ----------------------------------------------------------------

_armed_memo = (-1, False)


def armed():
    """True iff the cache may touch disk: ``FLAGS_serving_aot_cache``
    on AND ``FLAGS_aot_cache_dir`` non-empty. Memoized per flags epoch
    (one int compare on the warm path)."""
    global _armed_memo
    ep = flags_mod.epoch()
    memo = _armed_memo
    if memo[0] == ep:
        return memo[1]
    on = bool(flags_mod.flag("FLAGS_serving_aot_cache")) and \
        bool(flags_mod.flag("FLAGS_aot_cache_dir"))
    _armed_memo = (ep, on)
    return on


def cache_dir():
    """The configured store directory ('' when disarmed by dir)."""
    return os.path.expanduser(str(flags_mod.flag("FLAGS_aot_cache_dir")))


def configure(path):
    """Point the cache at ``path`` (the ``set_flags`` form — tests and
    operators; '' disarms)."""
    flags_mod.set_flags({"FLAGS_aot_cache_dir": "" if path is None
                         else str(path)})


# -- fingerprinting --------------------------------------------------------

def _backend_sig():
    try:
        import jax
        d = jax.devices()[0]
        return (f"{d.platform}/{getattr(d, 'device_kind', '?')}"
                f"x{jax.device_count()}")
    except Exception:  # noqa: BLE001 — a backendless probe still keys
        return "unknown"


def _config_sig():
    """Compilation-relevant jax config values that do NOT show up in
    the lowered text (x64 changes avals — belt and braces — matmul
    precision changes the compiled code, not the StableHLO)."""
    try:
        import jax
        return (f"x64={bool(jax.config.jax_enable_x64)};"
                f"mm={jax.config.jax_default_matmul_precision}")
    except Exception:  # noqa: BLE001
        return "cfg-unknown"


def fingerprint(tag, lowered_text):
    """Content address of one executable: jax version + backend +
    config + tag + the full lowered StableHLO text (jaxpr, avals, and
    every trace-visible flag are inside the text). Deterministic
    across processes — the cross-process reuse contract pinned by
    tools/router_gate.py."""
    import jax
    h = hashlib.blake2b(digest_size=20)
    for part in (jax.__version__, _backend_sig(), _config_sig(),
                 str(tag), lowered_text):
        h.update(part.encode())
        h.update(b"\x00")
    return h.hexdigest()


def entry_path(fp):
    return os.path.join(cache_dir(), fp + ".aotx")


# -- the on-disk store (checkpoint-v2 discipline) --------------------------

class _Corrupt(RuntimeError):
    """Entry failed validation — quarantine, never load."""


def _quarantine(path, why):
    """Rename a bad entry to ``*.corrupt-N`` (first free N — the
    checkpoint.py quarantine idiom) so the slot frees for a fresh
    store and the evidence survives for a post-mortem."""
    for n in range(1000):
        dst = f"{path}.corrupt-{n}"
        if not os.path.exists(dst):
            break
    try:
        os.replace(path, dst)
    except OSError:
        try:
            os.remove(path)
        except OSError:
            pass
    _c_quarantined.inc()
    resilience.degrade("aot_cache.corrupt",
                       detail=f"{os.path.basename(path)}: {why}")


def _load(fp):
    """Deserialize the entry for ``fp``; (compiled, meta) or (None,
    None) on miss. Validation failures quarantine and miss; transient
    I/O failures degrade and miss — both fall back to a normal
    compile, a wrong executable is never returned."""
    path = entry_path(fp)
    try:
        _faults.site("aot.load")
        with open(path, "rb") as f:
            raw = f.read()
    except FileNotFoundError:
        return None, None
    except Exception as e:  # noqa: BLE001 — transient IO: compile instead
        resilience.degrade("aot_cache.load", exc=e)
        return None, None
    t0 = time.perf_counter_ns()
    try:
        if len(raw) < len(MAGIC) + _HEADER.size:
            raise _Corrupt(f"short file ({len(raw)}B)")
        if raw[:len(MAGIC)] != MAGIC:
            raise _Corrupt("bad magic")
        crc_b, length = _HEADER.unpack_from(raw, len(MAGIC))
        payload = raw[len(MAGIC) + _HEADER.size:]
        if len(payload) != length:
            raise _Corrupt(f"length {len(payload)} != header {length}")
        if zlib.crc32(payload) != int.from_bytes(crc_b, "big"):
            raise _Corrupt("crc32 mismatch")
        meta = pickle.loads(payload)
        if not isinstance(meta, dict) or meta.get("format") != FORMAT \
                or meta.get("fingerprint") != fp:
            raise _Corrupt("metadata disagrees with filename")
        from jax.experimental import serialize_executable as _se
        compiled = _se.deserialize_and_load(
            meta["exe"], meta["in_tree"], meta["out_tree"],
            execution_devices=_devices_by_id(meta.get("device_ids")))
    except Exception as e:  # noqa: BLE001 — ANY load failure quarantines:
        # the entry claimed this fingerprint and could not deliver it
        _quarantine(path, f"{type(e).__name__}: {e}")
        return None, None
    _h_load_us.observe((time.perf_counter_ns() - t0) / 1000.0)
    _c_bytes.inc(len(raw))
    return compiled, meta


def _devices_by_id(ids):
    """The devices an entry was compiled for (None for an entry from
    before they were recorded: the loader then takes every device of the
    backend, which is right only where the program spans them all)."""
    if not ids:
        return None
    import jax
    by_id = {d.id: d for d in jax.devices()}
    return [by_id[i] for i in ids]  # KeyError quarantines the entry


def _store(fp, compiled, compile_s, tag):
    """Serialize + commit one entry: staged write, fsync, atomic
    ``os.replace`` — a crashed writer leaves a ``.tmp`` straggler,
    never a torn entry. Failures degrade and return; the caller keeps
    the compiled program either way."""
    path = entry_path(fp)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        _faults.site("aot.store")
        from jax.experimental import serialize_executable as _se
        exe, in_tree, out_tree = _se.serialize(compiled)
        payload = pickle.dumps(
            {"format": FORMAT, "fingerprint": fp, "tag": str(tag),
             "compile_s": float(compile_s), "ts": time.time(),
             "backend": _backend_sig(), "exe": exe,
             "in_tree": in_tree, "out_tree": out_tree,
             # a program for one device of eight must load onto one
             "device_ids": [d.id for d in compiled.runtime_executable()
                            .local_devices()]})
        os.makedirs(cache_dir(), exist_ok=True)
        blob = (MAGIC
                + _HEADER.pack(zlib.crc32(payload).to_bytes(4, "big"),
                               len(payload))
                + payload)
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except Exception as e:  # noqa: BLE001 — a full disk must not kill serving
        resilience.degrade("aot_cache.store", exc=e)
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False
    _c_stores.inc()
    _c_bytes.inc(len(blob))
    return True


# -- the wrapper -----------------------------------------------------------

def _leaf_sig(leaf):
    shp = getattr(leaf, "shape", None)
    if shp is None:
        # python scalars trace to value-independent weak avals: keying
        # by type keeps one entry per scalar KIND, not per value
        return ("py", type(leaf).__name__)
    return (tuple(shp), str(getattr(leaf, "dtype", "?")),
            bool(getattr(leaf, "weak_type", False)))


def _sig(args):
    # armed-path dispatch cost: a python tree_flatten + per-leaf tuple
    # per call (tens of µs on a real model's param list) against
    # millisecond-scale prefill/decode dispatches. Deliberate: an
    # identity/try-call fast path would have to catch aval mismatches
    # from Compiled, trading a measured overhead for a correctness
    # cliff; disarmed callers never reach here
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(_leaf_sig(leaf) for leaf in leaves))


class AOTFunction:
    """Shape-dispatching wrapper over a ``jax.jit`` callable.

    Disarmed (the production default until a cache dir is configured)
    every call forwards straight to the wrapped jitted function —
    plain-jax behavior, zero counters. Armed, calls dispatch on the
    argument signature (pytree structure + per-leaf shape/dtype/
    weak-type) to a per-process table of loaded executables; a novel
    signature lowers (python trace only), fingerprints, and loads-or-
    compiles through the on-disk store. Safe to call from multiple
    threads (the prepare step is locked; compiled executables are
    reusable concurrently, like jitted functions)."""

    __slots__ = ("_jitted", "tag", "_compiled", "_lock")

    def __init__(self, jitted, tag):
        self._jitted = jitted
        self.tag = str(tag)
        self._compiled = {}
        self._lock = threading.Lock()

    def __call__(self, *args):
        if not armed():
            return self._jitted(*args)
        key = _sig(args)
        compiled = self._compiled.get(key)
        if compiled is None:
            compiled = self._prepare(key, args)
        return compiled(*args)

    def _prepare(self, key, args):
        with self._lock:
            compiled = self._compiled.get(key)
            if compiled is not None:
                return compiled
            lowered = self._jitted.lower(*args)
            fp = fingerprint(self.tag, lowered.as_text())
            compiled, meta = _load(fp)
            if compiled is not None:
                _c_hits.inc()
                _note_saved(float(meta.get("compile_s", 0.0)))
            else:
                _c_misses.inc()
                t0 = time.perf_counter_ns()
                compiled = lowered.compile()
                compile_s = (time.perf_counter_ns() - t0) / 1e9
                _store(fp, compiled, compile_s, self.tag)
            self._compiled[key] = compiled
            return compiled


def wrap(jitted, tag):
    """Wrap a ``jax.jit`` callable for persistent AOT caching. Always
    returns an :class:`AOTFunction`; the per-call armed check makes
    the wrapper behave exactly like ``jitted`` until a cache dir is
    configured (and again the moment ``FLAGS_serving_aot_cache=0``)."""
    return AOTFunction(jitted, tag)
