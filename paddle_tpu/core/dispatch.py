"""Op dispatch: the bridge from eager Tensor calls to XLA.

Capability parity with the reference's generated dispatch chain
(`paddle/phi/api/generator/api_base.py:1300` kernel selection +
`eager_gen.py:321` ad_func node creation), collapsed into one function:
``apply`` runs the jnp/lax forward, and — when any floating input requires
grad — records a tape Node holding the `jax.vjp` pullback. There is no
kernel registry to search: XLA owns kernel selection per backend.

The steady-state path is organized around two caches (the reference
avoids this cost with generated C++ ad_func chains; we cache the
dispatch DECISION instead, the LazyTensor / PyTorch-2 per-call-site
specialization move):

- a **dispatch-plan cache**: ``(fn behavior key, per-arg
  kind/requires-grad signature, frozen statics/kwargs)`` -> a ``_Plan``
  holding the array/static positions, the diff set, and the already
  built lazy-cache key — warm call sites skip ``_freeze``, key hashing,
  and route selection entirely;
- an **epoch-gated settings snapshot** (``_GATE``): the per-op flag
  reads (``FLAGS_check_nan_inf``, ``FLAGS_eager_defer``), amp-enabled,
  and the op-stats hook are re-read only when ``core.flags._EPOCH``
  moves (``set_flags`` / ``auto_cast`` enter+exit / op-stats toggles
  bump it), so the hot path pays one int compare instead of locked
  registry lookups and per-call imports.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Callable

import jax
import numpy as np

from . import dtype as dtype_mod
from . import flags as flags_mod
from .autograd import Node, _grad_state, is_grad_enabled  # noqa: F401
from .tensor import Tensor

# profiler package imports only stdlib at module level — no cycle back
# into core; _recorder is the process-global host span store (never
# rebound) and metrics is the always-on counter registry
from ..profiler import _recorder as _prof
from ..profiler import metrics as _metrics

# dispatch-route counters (see docs/OBSERVABILITY.md): which of the five
# paths each op takes — pre-bound so the per-op cost is one locked add
_C_PATH_EAGER = _metrics.counter("dispatch.path.eager")
_C_PATH_JITFWD = _metrics.counter("dispatch.path.jitted_fwd")
_C_PATH_LAZY = _metrics.counter("dispatch.path.lazy_vjp")
_C_PATH_EAGER_VJP = _metrics.counter("dispatch.path.eager_vjp")
_C_PATH_DEFERRED = _metrics.counter("dispatch.path.deferred")
_C_FWD_HIT = _metrics.counter("dispatch.fwd_cache.hit")
_C_FWD_MISS = _metrics.counter("dispatch.fwd_cache.miss")
_C_FWD_EVICT = _metrics.counter("dispatch.fwd_cache.evictions")
_C_BWD_HIT = _metrics.counter("dispatch.bwd_cache.hit")
_C_BWD_MISS = _metrics.counter("dispatch.bwd_cache.miss")
_C_BWD_EVICT = _metrics.counter("dispatch.bwd_cache.evictions")
_C_PLAN_HIT = _metrics.counter("dispatch.plan_cache.hit")
_C_PLAN_MISS = _metrics.counter("dispatch.plan_cache.miss")
_C_PLAN_EVICT = _metrics.counter("dispatch.plan_cache.evictions")

# rejection reasons are a closed set on the dispatch path: pre-bound
# like the route counters (a get-or-create registry lookup per rejected
# op was measurable on the hot no-grad path); unknown reasons still
# lazily register so the registry stays the single source of truth
_C_EAGER_ONLY = {r: _metrics.counter(f"dispatch.eager_only.{r}")
                 for r in ("unhashable_key", "below_composite_threshold",
                           "nontraceable", "nondiff_output")}


def _count_eager_only(reason):
    """An op was rejected from the lazy/jitted caches: count it."""
    c = _C_EAGER_ONLY.get(reason)
    if c is None:
        c = _C_EAGER_ONLY[reason] = _metrics.counter(
            f"dispatch.eager_only.{reason}")
    c.inc()


# differentiability is a pure function of dtype and dtypes are a tiny
# closed set at runtime — memoized so the per-arg check is one dict hit
_DIFF_DTYPE: dict = {}


def _differentiable(dt) -> bool:
    r = _DIFF_DTYPE.get(dt)
    if r is None:
        r = _DIFF_DTYPE[dt] = bool(dtype_mod.is_floating_point(dt)
                                   or dtype_mod.is_complex(dt))
    return r


# ---------------------------------------------------------------------------
# epoch-gated settings snapshot
# ---------------------------------------------------------------------------

class _GateState(threading.local):
    """Per-thread snapshot of the per-op gating reads. ``epoch`` is the
    flags-module settings epoch the snapshot was taken at; amp state is
    thread-local, so the snapshot must be too (a toggle in one thread
    bumps the global epoch, and each thread refreshes against its OWN
    amp state)."""

    def __init__(self):
        self.epoch = -1  # sentinel: first op in every thread refreshes
        self.check_naninf = False
        self.eager_defer = True
        self.amp_enabled = False
        self.dbg_record = None  # amp.debugging.record_op when stats on


_GATE = _GateState()

# sibling modules bound once at the first gate refresh (module-level
# import would cycle through the package __init__ mid-load)
_amp_mod = None
_dbg_mod = None
_deferred_mod = None
_ARR_T = None  # the concrete jax device-array type (ArrayImpl)


def _refresh_gate(g):
    """Re-read every epoch-gated setting (rare: only after a flags
    mutation / autocast toggle / op-stats toggle, or a thread's first
    op). The epoch is read FIRST: a bump racing the value reads leaves
    a stale epoch behind, forcing another (correct) refresh next op."""
    global _amp_mod, _dbg_mod, _deferred_mod, _ARR_T
    e = flags_mod._EPOCH
    if _amp_mod is None:
        import jax.numpy as jnp
        from .. import amp as _a
        from ..amp import debugging as _d
        from . import deferred as _df
        _ARR_T = type(jnp.zeros(()))
        _amp_mod, _dbg_mod, _deferred_mod = _a, _d, _df
    g.check_naninf = bool(flags_mod.flag("FLAGS_check_nan_inf"))
    g.eager_defer = bool(flags_mod.flag("FLAGS_eager_defer"))
    g.amp_enabled = _amp_mod.amp_state().enabled
    g.dbg_record = _dbg_mod.record_op \
        if _dbg_mod._op_stats is not None else None
    g.epoch = e
    return g


def _wrap_out(o):
    """Wrap one op output: the slot-assignment fast constructor for the
    dominant device-array case, the validating ``Tensor`` constructor
    for everything else (tracers under jit, numpy, python scalars)."""
    if type(o) is _ARR_T:
        return Tensor._wrap(o)
    return Tensor(o)


# ---------------------------------------------------------------------------
# cached lazy backward (the dygraph hot path)
#
# jax.vjp at op-record time costs a full python linearize trace (~0.8ms/op),
# 28x the no-grad dispatch — the reference avoids the analogue with
# generated per-op GradNodes. Here: for cacheable op fns the forward runs
# plainly (no trace) and the pullback is a jax.jit'd function built ONCE
# per (fn, arg structure) that re-runs jax.vjp INSIDE jit at backward time
# (jit's aval cache amortizes it; under the compiled TrainStep retrace XLA
# CSEs the recomputed forward against the original, so no extra FLOPs).
#
# Cacheable = fn has no closure cells (excludes RNG-capturing closures like
# dropout — recompute must be deterministic) and kwargs/static args hash.
#
# Both caches are LRU (move-to-end on hit, evict oldest): a hot composite
# forward can't be evicted by a burst of one-shot keys.
# ---------------------------------------------------------------------------

_LAZY_BWD_CACHE: OrderedDict = OrderedDict()
_LAZY_FWD_CACHE: OrderedDict = OrderedDict()
_LAZY_BWD_CACHE_MAX = 2048
_EAGER_ONLY = object()  # negative entry: op rejected from the lazy path


def _lru_touch(cache, key):
    """Move a hit entry to the MRU end. Tolerates a plain-dict stand-in
    (tests monkeypatch the caches) and a racing eviction of the key."""
    try:
        cache.move_to_end(key)
    except (AttributeError, KeyError):
        pass


def _evict_oldest(cache, counter):
    """Drop the LRU entry (single atomic C call on OrderedDict); the
    fallback branch handles plain-dict stand-ins, where insertion order
    is the best available approximation."""
    try:
        cache.popitem(last=False)
        counter.inc()
    except KeyError:
        pass  # a racing eviction emptied the cache
    except TypeError:
        try:
            cache.pop(next(iter(cache)))
            counter.inc()
        except (KeyError, StopIteration, RuntimeError):
            pass


def _make_lazy_fwd(fn, n_payloads, arr_pos, statics, kwargs, was_tuple):
    statics_d = dict(statics)

    @jax.jit
    def fwd(*arrs):
        full = [None] * n_payloads
        for pos, a in zip(arr_pos, arrs):
            full[pos] = a
        for pos, s in statics_d.items():
            full[pos] = s
        out = fn(*full, **kwargs)
        if was_tuple:
            return tuple(out)
        return out

    return fwd


_NOT_CACHED = object()


def _fwd_cached_call(fn, payloads, kwargs):
    """No-grad/inference fallback (no dispatch plan): composite ops run
    through the same cached jitted forward the recording path uses
    (keyed with an empty diff set), instead of per-primitive eager
    dispatch. Returns ``(out, path)`` with out = _NOT_CACHED when the op
    is not (yet) eligible — the caller then runs the plain eager
    forward, and the second call onward hits the cache."""
    arr_pos, arrs, statics = [], [], []
    for i, p in enumerate(payloads):
        if isinstance(p, (jax.Array, np.ndarray)):
            arr_pos.append(i)
            arrs.append(p)
        else:
            statics.append((i, p))
    try:
        key = (_fn_key(fn), (), tuple(arr_pos),
               _freeze(tuple(statics)), _freeze(kwargs))
        hash(key)
    except (TypeError, ValueError):
        _count_eager_only("unhashable_key")
        return _NOT_CACHED, "eager"
    fwd = _LAZY_FWD_CACHE.get(key)
    if fwd is None:
        # probe on the first call (outside any timing-critical loop)
        _C_FWD_MISS.inc()
        out = fn(*payloads, **kwargs)
        _populate_fwd_cache(key, fn, len(payloads), tuple(arr_pos),
                            tuple(statics), kwargs,
                            isinstance(out, (tuple, list)), arrs)
        return out, "eager"
    if fwd is _EAGER_ONLY:
        return _NOT_CACHED, "eager"
    _C_FWD_HIT.inc()
    _lru_touch(_LAZY_FWD_CACHE, key)
    return fwd(*arrs), "jitted_fwd"


def _populate_fwd_cache(key, fn, n_payloads, arr_pos, statics, kwargs,
                        was_tuple, arrs):
    """Decide once per key whether the forward gets a cached jit: only
    COMPOSITE fns (>= 3 primitives) — one jit call costs about one eager
    op dispatch, so fusing pays from ~3 primitives up; single-primitive
    wrappers stay on the raw eager call. The probe binds statics exactly
    like _make_lazy_fwd so static payloads never reach the tracer."""
    if key in _LAZY_FWD_CACHE:
        return
    if len(_LAZY_FWD_CACHE) >= _LAZY_BWD_CACHE_MAX:
        _evict_oldest(_LAZY_FWD_CACHE, _C_FWD_EVICT)
    statics_d = dict(statics)

    def bound(*a):
        full = [None] * n_payloads
        for pos, arr in zip(arr_pos, a):
            full[pos] = arr
        for pos, s in statics_d.items():
            full[pos] = s
        return fn(*full, **kwargs)

    try:
        n_eqns = len(jax.make_jaxpr(bound)(*arrs).jaxpr.eqns)
        reject_reason = "below_composite_threshold"
    except Exception:  # noqa: BLE001 — non-traceable: stay eager
        n_eqns = 0
        reject_reason = "nontraceable"
    if n_eqns >= 3:
        _LAZY_FWD_CACHE[key] = _make_lazy_fwd(
            fn, n_payloads, arr_pos, statics, kwargs, was_tuple)
    else:
        _LAZY_FWD_CACHE[key] = _EAGER_ONLY
        _count_eager_only(reject_reason)


def _freeze(v):
    if isinstance(v, Tensor) or hasattr(v, "_data"):
        # a Tensor in a static arg/kwarg would hash by identity and bake
        # its current value into the cached jit — stale after rebind
        raise TypeError("tensor in static op argument")
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(e) for e in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


class _LazyVjp:
    """Pullback handle: defers tracing to the first backward, through a
    per-structure jitted function."""

    __slots__ = ("_bwd", "_arrs")

    def __init__(self, bwd, arrs):
        self._bwd = bwd
        self._arrs = tuple(arrs)

    def __call__(self, cts):
        return self._bwd(self._arrs, tuple(cts))


def _lazy_bwd_for(key, fn, n_payloads, diff_idx, arr_pos, statics,
                  kwargs, was_tuple):
    entry = _LAZY_BWD_CACHE.get(key)
    if entry is not None and entry is not _EAGER_ONLY:
        _C_BWD_HIT.inc()
        _lru_touch(_LAZY_BWD_CACHE, key)
        return entry
    _C_BWD_MISS.inc()
    statics_d = dict(statics)
    diff_idx = tuple(diff_idx)
    arr_pos = tuple(arr_pos)

    @jax.jit
    def bwd(arrs, cts):
        full = [None] * n_payloads
        for pos, a in zip(arr_pos, arrs):
            full[pos] = a
        for pos, s in statics_d.items():
            full[pos] = s

        def pure(*diff_vals):
            f2 = list(full)
            for pos, v in zip(diff_idx, diff_vals):
                f2[pos] = v
            out = fn(*f2, **kwargs)
            if was_tuple:
                return tuple(out)
            return (out,)

        _, vjp_fn = jax.vjp(pure, *[full[i] for i in diff_idx])
        return vjp_fn(cts)

    if len(_LAZY_BWD_CACHE) >= _LAZY_BWD_CACHE_MAX:
        _evict_oldest(_LAZY_BWD_CACHE, _C_BWD_EVICT)
    _LAZY_BWD_CACHE[key] = bwd
    return bwd


def _fn_key(fn, _seen=None):
    """Identity of fn's BEHAVIOR, not its object: per-call lambdas (the
    dominant op-wrapper pattern) share their code object, so keying on
    (code, defaults, closure cell values, referenced-global values) makes
    them cache-hit. Closure cells or globals holding arrays (e.g.
    dropout's RNG key) are unhashable and reject the op to the eager-vjp
    path — exactly the impure cases where backward recompute would be
    wrong."""
    if getattr(fn, "__self__", None) is not None:
        # bound methods: per-instance state isn't visible in
        # code/defaults/closure — don't risk cross-instance reuse
        raise TypeError("bound method")
    code = getattr(fn, "__code__", None)
    if code is None:
        return fn  # builtin / PjitFunction / ufunc: stable identity
    if _seen is None:
        _seen = set()
    if id(fn) in _seen:
        return ("cycle", code)
    _seen.add(id(fn))
    cells = getattr(fn, "__closure__", None) or ()
    vals = []
    for c in cells:
        v = c.cell_contents
        if callable(v) and getattr(v, "__code__", None) is not None \
                and getattr(v, "__self__", None) is None:
            # per-call inner lambdas (e.g. an activation built each
            # forward) share code — recurse instead of id-hashing, or
            # every call would be a fresh cache entry + XLA compile
            vals.append(_fn_key(v, _seen))
        else:
            # whitelist, not blacklist: a hashable custom object would be
            # keyed by identity while the first-seen fn gets baked into
            # the cached jitted backward — if it held tensor data
            # internally, backward would silently recompute stale values
            vals.append(_cell_key(v, _seen))
    # Globals are free variables too: same-code lambdas referencing a
    # rebindable module-level name (`m = inst.mul; lambda a: m(a)`) would
    # otherwise collide and replay the first binding's cached backward.
    # Same whitelist as cells: modules by identity, plain functions
    # recursed (their own globals/cells are part of the behavior),
    # values through _cell_key, everything else rejects to eager-vjp.
    gvals = []
    fglobals = getattr(fn, "__globals__", None)
    if fglobals is not None:
        import types as _types
        for nm in _global_load_names(code):
            if nm not in fglobals:
                continue  # resolves in builtins: stable
            v = fglobals[nm]
            if isinstance(v, _types.ModuleType):
                gvals.append((nm, v))  # identity; rebind changes the key
            elif callable(v) and getattr(v, "__code__", None) is not None \
                    and getattr(v, "__self__", None) is None:
                gvals.append((nm, _fn_key(v, _seen)))
            else:
                gvals.append((nm, _cell_key(v, _seen)))
    kwdefs = getattr(fn, "__kwdefaults__", None)
    if kwdefs:
        # keyword-only defaults are behavior too: same-code wrappers
        # differing only in `*, scale=s` would otherwise collide
        kwkey = tuple(sorted((k, _cell_key(v, _seen))
                             for k, v in kwdefs.items()))
    else:
        kwkey = None
    return (code, fn.__defaults__, kwkey, tuple(vals), tuple(gvals))


_CODE_GLOBAL_NAMES: dict = {}


def _global_load_names(code):
    """Names a code object truly loads as globals (LOAD_GLOBAL targets,
    recursively through nested code consts) — co_names would also list
    attribute names, and a collision with an unrelated module global
    (`obj.params` vs a module-level `params`) would wrongly key or even
    reject the op. Cached per code object: bytecode never changes."""
    names = _CODE_GLOBAL_NAMES.get(code)
    if names is None:
        import dis
        import types as _types
        found = set()
        stack = [code]
        while stack:
            c = stack.pop()
            for ins in dis.get_instructions(c):
                if ins.opname in ("LOAD_GLOBAL", "LOAD_NAME"):
                    found.add(ins.argval)
            for const in c.co_consts:
                if isinstance(const, _types.CodeType):
                    stack.append(const)
        names = tuple(sorted(found))
        _CODE_GLOBAL_NAMES[code] = names
    return names


_STABLE_CALLABLE_TYPES = None


def _stable_callable_types():
    global _STABLE_CALLABLE_TYPES
    if _STABLE_CALLABLE_TYPES is None:
        import types
        kinds = [types.BuiltinFunctionType, np.ufunc,
                 jax.custom_jvp, jax.custom_vjp]
        kinds.append(type(jax.jit(lambda: 0)))  # PjitFunction
        _STABLE_CALLABLE_TYPES = tuple(kinds)
    return _STABLE_CALLABLE_TYPES


def _cell_key(v, _seen=None):
    """Key for a closure-cell value: only value-semantics immutables and
    stable-identity callables are admitted; everything else rejects the
    op to the eager-vjp path."""
    if v is None or isinstance(v, (bool, int, float, complex, str, bytes)):
        return v
    if isinstance(v, (np.dtype, jax.sharding.Mesh)):
        return v  # immutable, hashed by value
    if isinstance(v, type) and issubclass(v, (np.generic, bool, int,
                                              float, complex)):
        # dtype-like classes only (jnp.float32 etc). An arbitrary class
        # would be keyed by identity while its MUTABLE class attributes
        # get baked into the cached jitted backward — stale after edits.
        return v
    if isinstance(v, tuple):
        return tuple(_cell_key(e, _seen) for e in v)
    if isinstance(v, frozenset):
        return frozenset(_cell_key(e, _seen) for e in v)
    if isinstance(v, slice):
        return ("slice", _cell_key(v.start, _seen),
                _cell_key(v.stop, _seen), _cell_key(v.step, _seen))
    import functools
    if isinstance(v, functools.partial):
        return ("partial", _cell_key_fn(v.func, _seen),
                tuple(_cell_key(a, _seen) for a in v.args),
                tuple(sorted((k, _cell_key(x, _seen))
                             for k, x in v.keywords.items())))
    if isinstance(v, _stable_callable_types()):
        # module-level stable identities (jnp builtins, jitted fns,
        # custom_jvp/vjp wrappers); rebinding the cell changes identity
        # and therefore the key
        return v
    raise TypeError(f"unsafe closure cell type {type(v).__name__}")


def _cell_key_fn(v, _seen=None):
    """Key a callable that may be a plain function or a stable builtin."""
    if getattr(v, "__code__", None) is not None \
            and getattr(v, "__self__", None) is None:
        return _fn_key(v, _seen)
    return _cell_key(v, _seen)


def _try_lazy_apply(fn, payloads, diff_idx, kwargs, name, check_naninf,
                    begin=None):
    """Diff fallback (no dispatch plan): plain eager forward + cached
    lazy pullback. Returns wrapped outputs, or None when the op is not
    cacheable."""
    arr_pos, arrs, statics = [], [], []
    for i, p in enumerate(payloads):
        if isinstance(p, (jax.Array, np.ndarray)):
            arr_pos.append(i)
            arrs.append(p)
        else:
            statics.append((i, p))
    try:
        key = (_fn_key(fn), tuple(diff_idx), tuple(arr_pos),
               _freeze(tuple(statics)), _freeze(kwargs))
        hash(key)
    except (TypeError, ValueError):
        _count_eager_only("unhashable_key")
        return None
    if _LAZY_BWD_CACHE.get(key) is _EAGER_ONLY:
        return None  # known non-diff-output op: skip the probe forward

    fwd = _LAZY_FWD_CACHE.get(key)
    if fwd is not None and fwd is not _EAGER_ONLY:
        # cached JITTED forward: a composite op (sdpa, layer_norm, ...)
        # runs as ONE fused XLA executable instead of op-by-op jax eager
        # dispatch — the eager-mode answer to the reference's fused
        # per-op kernels (phi/kernels/fusion). Same cacheability rules
        # as the lazy backward, so semantics are unchanged.
        _C_FWD_HIT.inc()
        _lru_touch(_LAZY_FWD_CACHE, key)
        out = fwd(*arrs)
        was_tuple = isinstance(out, (tuple, list))
        out_tuple = tuple(out) if was_tuple else (out,)
        _post_op_hooks(name, out_tuple, check_naninf, begin=begin,
                       path="lazy_vjp")
        bwd = _lazy_bwd_for(key, fn, len(payloads), diff_idx, arr_pos,
                            statics, kwargs, was_tuple)
        return out_tuple, _LazyVjp(bwd, arrs), was_tuple

    if fwd is None:
        _C_FWD_MISS.inc()  # probe forward below populates the cache
    out = fn(*payloads, **kwargs)
    was_tuple = isinstance(out, (tuple, list))
    out_tuple = tuple(out) if was_tuple else (out,)
    # float0 cotangents (non-float outputs) don't pass through jit args;
    # keep those ops on the eager-vjp path (memoized so later calls don't
    # pay a doubled forward)
    if not all(hasattr(o, "dtype") and _differentiable(o.dtype)
               for o in out_tuple):
        _LAZY_BWD_CACHE[key] = _EAGER_ONLY
        _count_eager_only("nondiff_output")
        return None
    _populate_fwd_cache(key, fn, len(payloads), tuple(arr_pos),
                        tuple(statics), kwargs, was_tuple, arrs)
    _post_op_hooks(name, out_tuple, check_naninf, begin=begin,
                   path="lazy_vjp")
    bwd = _lazy_bwd_for(key, fn, len(payloads), diff_idx, arr_pos,
                        statics, kwargs, was_tuple)
    return out_tuple, _LazyVjp(bwd, arrs), was_tuple


# ---------------------------------------------------------------------------
# dispatch-plan cache
# ---------------------------------------------------------------------------

# per-arg signature sentinels: an ARRAY operand (Tensor payload or raw
# array — identical for routing: a jit argument slot), a DIFF operand
# (recording, requires-grad, differentiable dtype), or a static whose
# FROZEN VALUE is part of the key (statics are baked into the cached
# forward exactly as in the lazy-cache keys)
_SIG_ARR = ("a",)
_SIG_DIFF = ("d",)

_PLAN_CACHE: OrderedDict = OrderedDict()
_PLAN_CACHE_MAX = 4096


class _Plan:
    """The precomputed dispatch decision for one call-site signature:
    where the arrays/statics sit, which args are differentiated, and the
    lazy-cache key those positions produce. Everything here is
    position/route information — VALUES (payloads, scalar statics) are
    taken from the live call, so a plan can never serve stale data."""

    __slots__ = ("n_args", "arr_pos", "static_pos", "diff_idx", "fwd_key")

    def __init__(self, n_args, arr_pos, static_pos, diff_idx, fwd_key):
        self.n_args = n_args
        self.arr_pos = arr_pos
        self.static_pos = static_pos
        self.diff_idx = diff_idx
        self.fwd_key = fwd_key


def _insert_plan(plan_key):
    """Build + insert the plan for a signature (one-time per call site);
    the derived ``fwd_key`` matches the legacy `_fwd_cached_call` /
    `_try_lazy_apply` key layout exactly, so plan and fallback paths
    share the same lazy-cache entries."""
    fnk, kwk = plan_key[0], plan_key[1]
    arr_pos, static_pos, diff_idx, statics_f = [], [], [], []
    for i in range(2, len(plan_key)):
        s = plan_key[i]
        if s is _SIG_ARR:
            arr_pos.append(i - 2)
        elif s is _SIG_DIFF:
            arr_pos.append(i - 2)
            diff_idx.append(i - 2)
        else:
            static_pos.append(i - 2)
            statics_f.append((i - 2, s[1]))
    fwd_key = (fnk, tuple(diff_idx), tuple(arr_pos), tuple(statics_f), kwk)
    plan = _Plan(len(plan_key) - 2, tuple(arr_pos), tuple(static_pos),
                 tuple(diff_idx), fwd_key)
    if len(_PLAN_CACHE) >= _PLAN_CACHE_MAX:
        _evict_oldest(_PLAN_CACHE, _C_PLAN_EVICT)
    _PLAN_CACHE[plan_key] = plan
    return plan


def _plan_apply_nograd(plan, fn, payloads, arrs, kwargs, name,
                       check_naninf, t0, g):
    """Steady-state no-grad dispatch: one lazy-cache get decides jitted
    vs eager; outputs wrap through the slot-assignment constructor."""
    fwd = _LAZY_FWD_CACHE.get(plan.fwd_key)
    if fwd is not None and fwd is not _EAGER_ONLY:
        _C_FWD_HIT.inc()
        _lru_touch(_LAZY_FWD_CACHE, plan.fwd_key)
        out = fwd(*arrs)
        _C_PATH_JITFWD.inc()
        path = "jitted_fwd"
    else:
        if fwd is None:
            _C_FWD_MISS.inc()
            out = fn(*payloads, **kwargs)
            _populate_fwd_cache(
                plan.fwd_key, fn, plan.n_args, plan.arr_pos,
                tuple((i, payloads[i]) for i in plan.static_pos),
                kwargs, isinstance(out, (tuple, list)), arrs)
        else:
            out = fn(*payloads, **kwargs)
        _C_PATH_EAGER.inc()
        path = "eager"
    if t0 is not None or check_naninf or g.dbg_record is not None:
        _post_op_hooks(name, out if isinstance(out, (tuple, list))
                       else (out,), check_naninf, begin=t0, path=path)
    if isinstance(out, (tuple, list)):
        return [_wrap_out(o) for o in out]
    return _wrap_out(out)


def _plan_apply_diff(plan, fn, args, payloads, arrs, kwargs, name,
                     check_naninf, t0, g):
    """Steady-state recording dispatch through the plan's prebuilt key:
    cached (or probing) forward + cached lazy pullback + tape Node.
    Returns _NOT_CACHED when the op must take the eager-vjp fallback
    (same rejections the legacy path enforces)."""
    key = plan.fwd_key
    if _LAZY_BWD_CACHE.get(key) is _EAGER_ONLY:
        return _NOT_CACHED
    fwd = _LAZY_FWD_CACHE.get(key)
    if fwd is not None and fwd is not _EAGER_ONLY:
        _C_FWD_HIT.inc()
        _lru_touch(_LAZY_FWD_CACHE, key)
        out = fwd(*arrs)
        was_tuple = isinstance(out, (tuple, list))
        out_tuple = tuple(out) if was_tuple else (out,)
    else:
        if fwd is None:
            _C_FWD_MISS.inc()
        out = fn(*payloads, **kwargs)
        was_tuple = isinstance(out, (tuple, list))
        out_tuple = tuple(out) if was_tuple else (out,)
        if not all(hasattr(o, "dtype") and _differentiable(o.dtype)
                   for o in out_tuple):
            _LAZY_BWD_CACHE[key] = _EAGER_ONLY
            _count_eager_only("nondiff_output")
            return _NOT_CACHED
        if fwd is None:
            _populate_fwd_cache(
                key, fn, plan.n_args, plan.arr_pos,
                tuple((i, payloads[i]) for i in plan.static_pos),
                kwargs, was_tuple, arrs)
    _C_PATH_LAZY.inc()
    if t0 is not None or check_naninf or g.dbg_record is not None:
        _post_op_hooks(name, out_tuple, check_naninf, begin=t0,
                       path="lazy_vjp")
    bwd = _lazy_bwd_for(key, fn, plan.n_args, plan.diff_idx, plan.arr_pos,
                        tuple((i, payloads[i]) for i in plan.static_pos),
                        kwargs, was_tuple)
    return _finish_recorded(fn, args, payloads, plan.diff_idx, kwargs,
                            out_tuple, _LazyVjp(bwd, arrs), was_tuple,
                            name)


def _finish_recorded(fn, args, payloads, diff_idx, kwargs, out_tuple,
                     vjp_fn, was_tuple, name):
    """Shared recording tail: tape Node + wrapped outputs."""
    out_meta = [(o.shape, o.dtype) for o in out_tuple]
    # fwd_fn: the node's pure forward over its diff inputs — what lets
    # create_graph=True re-record this op's backward differentiably
    def fwd_fn(*diff_vals):
        full = list(payloads)
        for pos, v in zip(diff_idx, diff_vals):
            full[pos] = v
        out = fn(*full, **kwargs)
        return tuple(out) if was_tuple else (out,)

    node = Node(vjp_fn, [args[i] for i in diff_idx], out_meta, name=name,
                fwd_fn=fwd_fn,
                primals=[payloads[i] for i in diff_idx])

    outs = []
    any_diff_out = False
    for idx, o in enumerate(out_tuple):
        t = _wrap_out(o)
        if _differentiable(o.dtype):
            t.stop_gradient = False
            t._node = node
            t._out_idx = idx
            any_diff_out = True
        outs.append(t)
    if not any_diff_out:
        for t in outs:
            t._node = None

    if was_tuple:
        return outs
    return outs[0]


def _eager_vjp_apply(fn, args, payloads, diff_idx, kwargs, name,
                     check_naninf, t0, g):
    """Per-call jax.vjp fallback for ops the lazy caches reject."""
    diff_args = [payloads[i] for i in diff_idx]
    was_tuple = [False]

    def pure(*diff_vals):
        full = list(payloads)
        for pos, v in zip(diff_idx, diff_vals):
            full[pos] = v
        out = fn(*full, **kwargs)
        if isinstance(out, (tuple, list)):
            was_tuple[0] = True
            return tuple(out)
        return (out,)

    out_tuple, vjp_fn = jax.vjp(pure, *diff_args)
    _C_PATH_EAGER_VJP.inc()
    if t0 is not None or check_naninf or g.dbg_record is not None:
        _post_op_hooks(name, out_tuple, check_naninf, begin=t0,
                       path="eager_vjp")
    return _finish_recorded(fn, args, payloads, diff_idx, kwargs,
                            out_tuple, vjp_fn, was_tuple[0], name)


def apply(fn: Callable, *args, name: str = None, defer: bool = False,
          **kwargs):
    """Run ``fn`` over the payloads of ``args`` and wrap outputs as Tensors.

    - Tensor args are unwrapped to jax arrays; non-Tensor args pass through.
    - If recording, differentiable Tensor args become jax.vjp arguments and a
      Node is attached to every differentiable output.
    - ``fn`` may return one array or a tuple/list of arrays; ``apply``
      returns a single Tensor or a list of Tensors accordingly.
    - ``defer=True`` marks a shape/dtype-preserving elementwise op as
      eligible for the deferred-chain dispatch (core/deferred.py): on a
      no-grad path the op joins a pending expression instead of
      dispatching, and the whole chain runs as one jitted program at the
      first ``_data`` read — one device round trip per chain.
    """
    # span begin: one clock read per op, only while a Profiler records
    t0 = time.perf_counter_ns() if _prof.enabled else None
    g = _GATE
    if g.epoch != flags_mod._EPOCH:
        _refresh_gate(g)
    name = name or getattr(fn, "__name__", "op")
    if g.amp_enabled:
        args = _amp_mod.amp_dispatch_pre(name, args)
    check_naninf = g.check_naninf
    recording = _grad_state.enabled
    if defer and not check_naninf and g.eager_defer:
        expr = _deferred_mod.try_defer(fn, args, kwargs, recording)
        if expr is not None:
            _C_PATH_DEFERRED.inc()
            if t0 is not None or g.dbg_record is not None:
                _post_op_hooks(
                    name,
                    (_deferred_mod._DtypeOnly(expr.dtype, expr.shape),),
                    False, begin=t0, path="deferred")
            return Tensor._from_pending(expr)

    # -- plan fast path: one signature build + one OrderedDict get ------
    payloads = None
    plan = None
    try:
        nargs = len(args)
        if nargs == 1:
            # unary specialization: no intermediate lists on the
            # dominant 1-Tensor-arg shape; the pending check inlines
            # Tensor._data's fast path (plain _buf read when no chain)
            a0 = args[0]
            if isinstance(a0, Tensor):
                if a0._pending is None:
                    p0 = a0._buf
                else:
                    _deferred_mod.note_flush_cause("op_boundary",
                                                   weak=True)
                    p0 = a0._data
                s0 = _SIG_DIFF if (recording and not a0.stop_gradient
                                   and _differentiable(p0.dtype)) \
                    else _SIG_ARR
            elif isinstance(a0, (jax.Array, np.ndarray)):
                p0, s0 = a0, _SIG_ARR
            else:
                p0, s0 = a0, ("s", _freeze(a0))
            plan_key = (_fn_key(fn), _freeze(kwargs) if kwargs else (),
                        s0)
            payloads = (p0,)
            arrs = () if s0[0] == "s" else payloads
        elif nargs == 2:
            # binary specialization (x op y, x op scalar)
            a0, a1 = args
            if isinstance(a0, Tensor):
                if a0._pending is None:
                    p0 = a0._buf
                else:
                    _deferred_mod.note_flush_cause("op_boundary",
                                                   weak=True)
                    p0 = a0._data
                s0 = _SIG_DIFF if (recording and not a0.stop_gradient
                                   and _differentiable(p0.dtype)) \
                    else _SIG_ARR
            elif isinstance(a0, (jax.Array, np.ndarray)):
                p0, s0 = a0, _SIG_ARR
            else:
                p0, s0 = a0, ("s", _freeze(a0))
            if isinstance(a1, Tensor):
                if a1._pending is None:
                    p1 = a1._buf
                else:
                    _deferred_mod.note_flush_cause("op_boundary",
                                                   weak=True)
                    p1 = a1._data
                s1 = _SIG_DIFF if (recording and not a1.stop_gradient
                                   and _differentiable(p1.dtype)) \
                    else _SIG_ARR
            elif isinstance(a1, (jax.Array, np.ndarray)):
                p1, s1 = a1, _SIG_ARR
            else:
                p1, s1 = a1, ("s", _freeze(a1))
            plan_key = (_fn_key(fn), _freeze(kwargs) if kwargs else (),
                        s0, s1)
            payloads = (p0, p1)
            if s0[0] == "s":
                arrs = () if s1[0] == "s" else (p1,)
            elif s1[0] == "s":
                arrs = (p0,)
            else:
                arrs = payloads
        else:
            sig = [_fn_key(fn), _freeze(kwargs) if kwargs else ()]
            payloads = []
            arrs = []
            for a in args:
                if isinstance(a, Tensor):
                    if a._pending is not None:
                        _deferred_mod.note_flush_cause("op_boundary",
                                                       weak=True)
                    p = a._data
                    payloads.append(p)
                    arrs.append(p)
                    sig.append(
                        _SIG_DIFF if (recording and not a.stop_gradient
                                      and _differentiable(p.dtype))
                        else _SIG_ARR)
                elif isinstance(a, (jax.Array, np.ndarray)):
                    payloads.append(a)
                    arrs.append(a)
                    sig.append(_SIG_ARR)
                else:
                    payloads.append(a)
                    sig.append(("s", _freeze(a)))
            plan_key = tuple(sig)
        plan = _PLAN_CACHE.get(plan_key)
        if plan is None:
            _C_PLAN_MISS.inc()
            plan = _insert_plan(plan_key)
        else:
            # no per-hit LRU touch: it would re-hash the key every op,
            # and a plan evicted by FIFO churn rebuilds in ~µs (unlike
            # the lazy caches, where eviction costs a retrace)
            _C_PLAN_HIT.inc()
    except (TypeError, ValueError):
        plan = None  # unplannable signature: legacy fallback below

    if plan is not None:
        if not plan.diff_idx:
            return _plan_apply_nograd(plan, fn, payloads, arrs, kwargs,
                                      name, check_naninf, t0, g)
        out = _plan_apply_diff(plan, fn, args, payloads, arrs, kwargs,
                               name, check_naninf, t0, g)
        if out is not _NOT_CACHED:
            return out
        return _eager_vjp_apply(fn, args, payloads, plan.diff_idx,
                                kwargs, name, check_naninf, t0, g)

    # -- fallback: unplannable fn/args (unhashable key, bound method,
    # tensor-in-static, ...) — the pre-plan dispatch logic, preserving
    # every cacheability rejection and counter exactly ------------------
    diff_idx = []
    payloads = []
    for i, a in enumerate(args):
        if isinstance(a, Tensor):
            if a._pending is not None:
                _deferred_mod.note_flush_cause("op_boundary", weak=True)
            payloads.append(a._data)
            if recording and not a.stop_gradient and \
                    _differentiable(a._data.dtype):
                diff_idx.append(i)
        else:
            payloads.append(a)

    if not diff_idx:
        out, path = _fwd_cached_call(fn, payloads, kwargs)
        if out is _NOT_CACHED:
            out = fn(*payloads, **kwargs)
        (_C_PATH_JITFWD if path == "jitted_fwd" else _C_PATH_EAGER).inc()
        _post_op_hooks(name, out if isinstance(out, (tuple, list))
                       else (out,), check_naninf, begin=t0, path=path)
        if isinstance(out, (tuple, list)):
            return [_wrap_out(o) for o in out]
        return _wrap_out(out)

    lazy = _try_lazy_apply(fn, payloads, diff_idx, kwargs, name,
                           check_naninf, begin=t0)
    if lazy is not None:
        _C_PATH_LAZY.inc()
        out_tuple, vjp_fn, was_tuple = lazy
        return _finish_recorded(fn, args, payloads, diff_idx, kwargs,
                                out_tuple, vjp_fn, was_tuple, name)
    return _eager_vjp_apply(fn, args, payloads, diff_idx, kwargs, name,
                            check_naninf, t0, g)


def _post_op_hooks(name, outs, check_naninf, begin=None, path="eager"):
    """Per-op post hooks: NaN/Inf sanitizer (FLAGS_check_nan_inf — the
    generated-ad_func CheckTensorHasNanOrInf analogue), AMP op-stats, and
    profiler op spans (the generated ad_funcs' RecordEvent analogue).

    ``begin`` is the perf_counter_ns captured at ``apply`` entry — the
    span covers the full dispatch (unwrap, cache lookups, the jax call),
    so Operator events carry REAL durations, begin/end style. ``path``
    labels which dispatch route ran (eager / jitted_fwd / lazy_vjp /
    eager_vjp / deferred) and lands in the span args.

    The op-stats probe is the epoch-gated ``_GATE.dbg_record`` snapshot
    (refreshed by apply before this runs) — the old per-op ``import
    sys`` + ``sys.modules.get`` probe was pure hot-path overhead."""
    if _prof.enabled:
        end = time.perf_counter_ns() / 1000.0
        start = end if begin is None else begin / 1000.0
        span_args = {"path": path}
        if _prof.record_shapes:
            span_args["shapes"] = [
                list(getattr(o, "shape", ())) for o in outs]
            span_args["dtypes"] = [
                str(getattr(o, "dtype", "?")) for o in outs]
        _prof.record(name, start, end, "Operator", span_args)

    rec = _GATE.dbg_record
    if rec is not None:
        for o in outs:
            if hasattr(o, "dtype"):
                rec(name, o.dtype)
                break
    if check_naninf:
        dbg = _dbg_mod
        if dbg is None:
            from ..amp import debugging as dbg
        for o in outs:
            if hasattr(o, "dtype"):
                dbg.check_array(name, o)


def unwrap(x):
    return x._data if isinstance(x, Tensor) else x


def as_index(arr):
    """Downcast an integer index array to int32 for use inside traced
    programs.

    The API surface keeps paddle's default int64 (jax_enable_x64), but index
    operands of gather/scatter-family ops are bounded by array dimensions
    (< 2^31), and int32 indices are both faster on TPU (s64 is emulated) and
    required to sidestep an XLA SPMD-partitioner check failure when s64
    index tensors cross a sharded boundary (spmd_partitioner_util.h:117).
    """
    import jax.numpy as jnp

    if hasattr(arr, "dtype") and jnp.issubdtype(arr.dtype, jnp.integer) \
            and arr.dtype != jnp.int32:
        return arr.astype(jnp.int32)
    return arr
