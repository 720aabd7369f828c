"""Deferred elementwise chains: batch consecutive eager ops into ONE
device dispatch — and overlap that dispatch with host-side capture.

Every eager dispatch pays the host's per-op cost (bench.py
`_dispatch_breakdown` splits it), so a dependent chain like
``y = y * a + b`` in a python loop is dispatch-bound however fast the
device is. The reference hides per-op latency with its async eager
executor (SURVEY §3.1: ad_func enqueue + device streams); the XLA-native
equivalent is to not dispatch per op at all: shape/dtype-preserving
elementwise ops on no-grad tensors accumulate into a small expression
DAG, and the chain executes as ONE jitted XLA program — keyed by chain
STRUCTURE (scalar constants ride as 0-d jit arguments, so loop-varying
scalars do NOT recompile), so steady-state loops hit the jit cache and
pay one dispatch per `DEFER_CAP` ops.

Async flush (``FLAGS_deferred_async``, default on): when a chain hits
``DEFER_CAP`` the capture thread does NOT stop to execute it — the
chain is submitted to a single background flush worker, its outputs
become :class:`ChainFuture` placeholders (carrying declared
shape/dtype, so meta reads stay lazy), and capture continues into a
fresh chain whose leaves are those futures. The worker drains
submissions FIFO — a future used as a later chain's leaf is always
materialized before that chain runs — under a bounded in-flight window
(``FLAGS_deferred_inflight``): submission blocks when the window is
full (counted ``deferred.async.window_full``), so an unbounded python
loop cannot race ahead of the device. Host reads
(``Tensor._data``/``.numpy()``) resolve futures lazily.

Semantics are preserved by construction:
- only ops explicitly marked ``defer=True`` in the op library enter a
  chain (same-shape/same-float-dtype elementwise, python scalars ok);
- any read of ``Tensor._data`` (numpy(), item(), an undeferrable op,
  autograd, jit boundaries) flushes the chain first — and resolves any
  pending async result — so no user-visible laziness beyond what jax's
  own async dispatch already has;
- a flush stamps the value of every chain node still owned by a LIVE
  Tensor, so shared subexpressions are never re-executed;
- gradients never defer: ops with diff inputs take the tape path in
  ``dispatch.apply`` before deferral is consulted;
- under jit tracing payloads are Tracers and deferral bails out.

Flags: ``FLAGS_eager_defer`` (default on; env ``FLAGS_eager_defer=0``),
``FLAGS_deferred_async`` / ``FLAGS_deferred_inflight`` (async window),
``FLAGS_deferred_passes`` / ``FLAGS_deferred_fusion`` (pass pipeline).
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from . import flags as flags_mod
from . import resilience as _resilience
from ..profiler import _recorder as _prof
from ..profiler import metrics as _metrics
from ..profiler import tracing as _tracing
from ..testing import faults as _faults

# dispatch/tensor bindings resolved once at first use (module-level
# import would cycle: dispatch itself lazily imports this module) —
# try_defer runs per deferrable op, so the old per-call
# ``from .dispatch import ...`` import-machinery hits were hot-path cost
_fn_key = None
_freeze = None
_Tensor = None


def _bind_dispatch():
    global _fn_key, _freeze, _Tensor
    from .dispatch import _fn_key as fk, _freeze as fz
    from .tensor import Tensor
    _fn_key, _freeze, _Tensor = fk, fz, Tensor

DEFER_CAP = 64  # max unique nodes per chain before forced materialization

# true LRU (PR 3 `_LAZY_FWD/_BWD` treatment): hits move-to-end under the
# lock, eviction pops the least-recently-USED entry — a steady-state hot
# chain survives a burst of one-shot chain shapes
_JIT_CACHE: OrderedDict = OrderedDict()
_JIT_CACHE_MAX = 512
# chains are built thread-locally (one per tensor graph) but _JIT_CACHE
# and _CONST_MEMO are process-global: eviction at the cap is
# iterate-then-pop and two racing flushes could StopIteration/KeyError a
# worker thread — all structural mutation goes through this lock
_CACHE_LOCK = threading.Lock()

_C_EAGER_REPLAY = _metrics.counter("deferred.flush.eager_replay")
_C_JIT_HIT = _metrics.counter("deferred.jit_cache.hit")
_C_JIT_COMPILE = _metrics.counter("deferred.jit_cache.compiles")
_C_JIT_EVICT = _metrics.counter("deferred.jit_cache.evictions")
_H_CHAIN_LEN = _metrics.histogram("deferred.chain_len")
_H_COMPILE_US = _metrics.histogram(
    "deferred.compile_us",
    bounds=(100, 1000, 10_000, 100_000, 1_000_000, 10_000_000))

_C_ASYNC_SUBMIT = _metrics.counter("deferred.async.submitted")
_C_ASYNC_RESOLVED = _metrics.counter("deferred.async.resolved")
_C_ASYNC_WINDOW_FULL = _metrics.counter("deferred.async.window_full")

# why the chain materialized — stamped by the site that triggers the
# flush (dispatch.apply marks op boundaries; plain _data reads default
# to data_read). THREAD-LOCAL: concurrent serving engines flush from
# their own threads, and a process-global slot let one engine's
# op_boundary stamp mislabel another's cap flush (the old comment
# admitted as much) — each thread now labels only its own next flush.
_CAUSE_TLS = threading.local()


def note_flush_cause(cause, weak=False):
    """Label the NEXT flush on THIS thread (consumed and reset by
    flush()). A ``weak`` stamp never overrides an already-pending
    non-default cause — the op-boundary loop in dispatch.apply stamps
    weakly so it can't clobber the more specific ``cap`` label set by
    try_defer."""
    if weak and getattr(_CAUSE_TLS, "cause", "data_read") != "data_read":
        return
    _CAUSE_TLS.cause = cause


def _take_cause():
    c = getattr(_CAUSE_TLS, "cause", "data_read")
    _CAUSE_TLS.cause = "data_read"
    return c


# flush causes and reject reasons are closed sets on the per-op dispatch
# path: pre-bound like the _C_PATH_* counters in dispatch.py so each
# event costs one dict hit + locked add, not an f-string + registry get
_C_FLUSH = {c: _metrics.counter(f"deferred.flush.{c}")
            for c in ("data_read", "op_boundary", "cap")}
_C_REJECT = {r: _metrics.counter(f"deferred.reject.{r}")
             for r in ("grad", "tracer", "payload", "dtype",
                       "dtype_mismatch", "shape_mismatch", "arg_type",
                       "no_tensor_arg", "unhashable")}
# "cap" left the reject set in PR 10: the DEFER_CAP boundary now keeps
# deferring (async submit / inline flush of the over-cap args) instead
# of rejecting the boundary op — the label lives on as a FLUSH cause


def _count_flush(cause, n_nodes):
    _C_FLUSH[cause].inc()
    _H_CHAIN_LEN.observe(n_nodes)


def _count_reject(reason):
    """try_defer bailed: the op falls back to normal dispatch."""
    _C_REJECT[reason].inc()


class Expr:
    """One deferred op node: fn applied to (leaf | node | const) args."""

    __slots__ = ("fn", "argspec", "kwargs", "shape", "dtype", "n_nodes",
                 "value", "owner", "node_key", "__weakref__")

    def __init__(self, fn, argspec, kwargs, shape, dtype, n_nodes,
                 node_key):
        self.fn = fn
        self.argspec = argspec  # (("leaf", arr)|("node", Expr)|("const", v), ...)
        self.kwargs = kwargs
        self.shape = shape
        self.dtype = dtype
        self.n_nodes = n_nodes  # additive upper bound (see try_defer)
        self.value = None  # stamped after a flush (array or ChainFuture)
        self.owner = None  # weakref to the Tensor holding this node
        self.node_key = node_key  # (fn_key, frozen kwargs), built once


class _DtypeOnly:
    """Minimal out-descriptor for _post_op_hooks at defer time (AMP
    op-stats record the declared dtype, profiler spans the declared
    shape; there is no array yet)."""

    __slots__ = ("dtype", "shape")

    def __init__(self, dtype, shape=()):
        self.dtype = dtype
        self.shape = shape


def enabled():
    return bool(flags_mod.flag("FLAGS_eager_defer"))


def passes_enabled():
    """Graph-optimization pass pipeline toggle (paddle_tpu/passes):
    ``FLAGS_deferred_passes`` / env ``PADDLE_TPU_PASSES=0`` reverts
    flush to the verbatim (capture-order) compile path."""
    return bool(flags_mod.flag("FLAGS_deferred_passes"))


def fusion_enabled():
    """Fusion tier toggle (batch + fuse passes, passes/v2 cache
    namespace): ``FLAGS_deferred_fusion`` / env ``PADDLE_TPU_FUSION=0``
    keeps the cleanup-only passes/v1 pipeline."""
    return bool(flags_mod.flag("FLAGS_deferred_fusion"))


def async_enabled():
    """Async flush toggle: consulted only at the DEFER_CAP boundary
    (rare relative to per-op dispatch), so a plain flag read suffices."""
    return bool(flags_mod.flag("FLAGS_deferred_async"))


def _peek(t):
    """A Tensor's payload WITHOUT materializing: Expr | ChainFuture |
    jax.Array."""
    pend = getattr(t, "_pending", None)
    if pend is not None:
        return pend if pend.value is None else pend.value
    return t._buf


def _unique_count(roots):
    seen = set()
    stack = list(roots)
    while stack:
        e = stack.pop()
        if id(e) in seen:
            continue
        seen.add(id(e))
        for kind, v in e.argspec:
            if kind == "node" and v.value is None:
                stack.append(v)
    return len(seen)


def try_defer(fn, args, kwargs, recording):
    """Build an Expr for fn(*args) if every condition holds, else None.

    args are the ORIGINAL apply() args (Tensors / scalars); kwargs must
    freeze hashable. Returns an Expr carrying the declared out meta.

    At the DEFER_CAP boundary the over-cap argument chains materialize
    (cause "cap") and the op defers into a FRESH chain over their
    results — asynchronously via the flush worker by default, inline
    when ``FLAGS_deferred_async=0``; the partition boundaries are
    identical either way (see the cap branch below)."""
    if _Tensor is None:
        _bind_dispatch()
    Tensor = _Tensor

    shape = None
    dtype = None
    argspec = []
    n_nodes = 1
    for a in args:
        if isinstance(a, Tensor):
            if recording and not a.stop_gradient:
                _count_reject("grad")
                return None  # diff input: tape path owns it
            p = _peek(a)
            if isinstance(p, jax.core.Tracer):
                _count_reject("tracer")
                return None  # under jit tracing: no deferral
            if isinstance(p, Expr):
                s, dt = p.shape, p.dtype
                n_nodes += p.n_nodes
                argspec.append(("node", p))
            elif isinstance(p, ChainFuture):
                # async-flushed chain output: a leaf with declared meta
                s, dt = p.shape, p.dtype
                argspec.append(("leaf", p))
            elif isinstance(p, jax.Array):
                s, dt = p.shape, p.dtype
                argspec.append(("leaf", p))
            else:  # unexpected payload
                _count_reject("payload")
                return None
            if not jnp.issubdtype(dt, jnp.floating):
                _count_reject("dtype")
                return None
            if dtype is None:
                dtype = dt
            elif dt != dtype:
                _count_reject("dtype_mismatch")
                return None  # no implicit promotion in chains
            if s == ():
                pass  # same-dtype 0-d tensor: broadcast-neutral leaf
            elif shape is None:
                shape = s
            elif s != shape:
                _count_reject("shape_mismatch")
                return None  # no implicit (shape-changing) broadcast
        elif isinstance(a, (bool, int, float)) and not isinstance(
                a, np.generic):
            argspec.append(("const", float(a)))
        elif isinstance(a, (np.integer, np.floating)):
            argspec.append(("const", float(a)))
        else:
            _count_reject("arg_type")
            return None
    if dtype is None:
        _count_reject("no_tensor_arg")
        return None
    if shape is None:
        shape = ()  # every arg 0-d: the result is 0-d
    if n_nodes > DEFER_CAP:
        # the additive count double-counts shared nodes (y = y * y);
        # pay the exact traversal — ONE shared visited-set across all
        # args — only when the estimate trips the cap
        n_nodes = 1 + _unique_count(
            [v for k, v in argspec if k == "node"])
        if n_nodes > DEFER_CAP:
            # materialize the over-cap argument chains and keep
            # DEFERRING the boundary op into a fresh chain over their
            # results. Async (default): the chains go to the flush
            # worker and the results are futures — capture overlaps
            # execution. Sync (``FLAGS_deferred_async=0``): the chains
            # flush inline. Both modes partition the op stream at the
            # SAME boundaries into the SAME chain structures (a future
            # leaf and an array leaf share one cache key), so flipping
            # the flag is byte-for-byte — partition-dependent XLA
            # contraction (the FMA caveat, docs/ROBUSTNESS.md) never
            # enters the comparison.
            use_async = async_enabled()
            spec = []
            for kind, v in argspec:
                if kind != "node":
                    spec.append((kind, v))
                elif use_async:
                    spec.append(("leaf", flush_async(v, cause="cap")))
                else:
                    note_flush_cause("cap")
                    spec.append(("leaf", flush(v)))
            argspec = spec
            n_nodes = 1
    try:
        node_key = (_fn_key(fn), _freeze(kwargs))
        hash(node_key)
    except (TypeError, ValueError):
        _count_reject("unhashable")
        return None
    return Expr(fn, tuple(argspec), kwargs, shape, dtype, n_nodes,
                node_key)


def _buffer_key(v):
    """Secondary leaf-dedup key: the underlying device buffer. Distinct
    jax.Array wrappers can share one buffer (e.g. ``addressable_data``
    views handed out by distributed code); keying on the buffer pointer
    gives CSE one leaf index per array instead of one per wrapper. None
    when the array doesn't expose a stable pointer (sharded/committed
    elsewhere — or a ChainFuture leaf) — id-dedup still applies."""
    try:
        return ("buf", v.unsafe_buffer_pointer(), v.shape, str(v.dtype))
    except Exception:  # noqa: BLE001 — probe, not a contract
        return None


def _linearize(root):
    """Postorder-unique (nodes, leaves, consts): leaves deduped by array
    id, then by underlying buffer; consts collected as jit ARGUMENTS
    (values stay out of the cache key, so loop-varying scalars don't
    recompile). Leaves may be ChainFutures (async-flushed upstream
    chains) — resolved to arrays just before execution."""
    nodes, leaves, consts = [], [], []
    node_ix, leaf_ix, const_ix = {}, {}, {}

    def visit(e):
        if id(e) in node_ix:
            return node_ix[id(e)]
        spec = []
        for kind, v in e.argspec:
            if kind == "node":
                if v.value is not None:  # flushed since: now a leaf
                    kind, v = "leaf", v.value
                else:
                    spec.append(("node", visit(v)))
                    continue
            if kind == "leaf":
                ix = leaf_ix.get(id(v))
                if ix is None:
                    bk = _buffer_key(v)
                    if bk is not None:
                        ix = leaf_ix.get(bk)
                    if ix is None:
                        ix = len(leaves)
                        leaves.append(v)
                        if bk is not None:
                            leaf_ix[bk] = ix
                    leaf_ix[id(v)] = ix
                spec.append(("leaf", ix))
            else:
                # dedupe by value (repr keeps -0.0 distinct): a loop
                # reusing two scalars must pass 2 jit args, not one per
                # occurrence — jit call overhead scales with arg count
                ci = const_ix.get(repr(v))
                if ci is None:
                    ci = const_ix[repr(v)] = len(consts)
                    consts.append(v)
                spec.append(("const", ci))
        nodes.append((e, tuple(spec)))
        node_ix[id(e)] = len(nodes) - 1
        return node_ix[id(e)]

    visit(root)
    return nodes, leaves, consts


def _jit_cache_get(key):
    """LRU-touching lookup: a hit moves the entry to the MRU end so
    at-cap eviction pops the genuinely least-recently-used chain."""
    with _CACHE_LOCK:
        jf = _JIT_CACHE.get(key)
        if jf is not None:
            _JIT_CACHE.move_to_end(key)
        return jf


def _jit_cache_insert(key, jf):
    """Insert under the cache lock with at-cap LRU eviction; returns the
    winning callable and whether OUR ``jf`` won (a racing flush may have
    inserted the same key first — only the winner counts the compile and
    times the first call)."""
    with _CACHE_LOCK:
        if key not in _JIT_CACHE and len(_JIT_CACHE) >= _JIT_CACHE_MAX:
            try:
                _JIT_CACHE.popitem(last=False)
                _C_JIT_EVICT.inc()
            except KeyError:
                pass  # a racing flush already evicted
        won = _JIT_CACHE.setdefault(key, jf)
        if won is not jf:
            _JIT_CACHE.move_to_end(key)
        return won, won is jf


def _eval_chain(descr, leaf_arrays, const_arrays):
    """THE chain interpreter every flush rung runs: evaluate ``descr``
    (``(fn, spec, kwargs)`` in topological order, each spec a list of
    ``(kind, index)`` refs) over leaf/const arrays; returns all value
    slots. Verbatim and pass-optimized flushes trace it under jit and
    the rung-2 eager replay calls it directly — the ladder's fidelity
    contract is judged against exactly this evaluation, so a fix
    applied to a private copy of the loop would silently break it."""
    vals = []
    for fn, spec, kw in descr:
        argv = [leaf_arrays[ix] if kind == "leaf" else
                vals[ix] if kind == "node" else const_arrays[ix]
                for kind, ix in spec]
        vals.append(fn(*argv, **kw))
    return vals


def _build_chain_jf(descr, n_leaves, out_ixs):
    """Jit-wrap ``_eval_chain`` returning the ``out_ixs`` slots — what
    both compile paths cache."""

    @jax.jit
    def jf(*arrs):
        vals = _eval_chain(descr, arrs[:n_leaves], arrs[n_leaves:])
        return tuple(vals[i] for i in out_ixs)

    return jf


def _maybe_aot_wrap(jf, label):
    """Route a FRESH chain program through the persistent AOT compile
    cache (serving/aot_cache.py) — a new process with a warm cache
    replays its steady-state chains without one XLA compile. Wrapped
    unconditionally, like the llama entry points: AOTFunction checks
    arming per call (one epoch-memoized flag read), so a chain built
    before the operator configures the cache dir still participates
    once armed, and the disarmed path forwards straight to the plain
    jitted callable — byte-for-byte pre-cache."""
    try:
        from ..serving.aot_cache import wrap
        return wrap(jf, tag=label)
    except Exception:  # noqa: BLE001 — caching must never break a flush
        return jf


def _timed_first_call(jf, args):
    """First call of a fresh jf pays trace+compile: time it (the
    jax.monitoring listener in profiler.metrics counts the true backend
    compiles; this is the end-to-end chain-build cost)."""
    tc = time.perf_counter_ns()
    outs = jf(*args)
    _C_JIT_COMPILE.inc()
    _H_COMPILE_US.observe((time.perf_counter_ns() - tc) / 1000.0)
    return outs


def _run_chain(jf, args, fresh):
    """Execute a (possibly fresh) chain program. The injection site is
    where a real backend failure surfaces — jax traces/compiles on the
    first call and can raise RESOURCE_EXHAUSTED from either."""
    _faults.site("deferred.compile")
    return _timed_first_call(jf, args) if fresh else jf(*args)


# -- async flush -----------------------------------------------------------

class _Submission:
    """One async-flushed chain: the captured linearization, the worker's
    result slots, and the finalize latch that stamps Expr values."""

    __slots__ = ("nodes", "leaves", "consts", "out_ixs", "cause",
                 "dtype", "ctx", "event", "values", "exc", "flock",
                 "finalized")

    def __init__(self, nodes, leaves, consts, out_ixs, cause, dtype):
        self.nodes = nodes
        self.leaves = leaves
        self.consts = consts
        self.out_ixs = out_ixs
        self.cause = cause
        self.dtype = dtype
        self.ctx = _tracing.current_context()
        self.event = threading.Event()
        self.values = None
        self.exc = None
        self.flock = threading.Lock()
        self.finalized = False

    def finalize(self):
        """Stamp every out Expr with its concrete value (idempotent).
        Counted once per submission as ``deferred.async.resolved``."""
        with self.flock:
            if self.finalized:
                return
            for slot, i in enumerate(self.out_ixs):
                self.nodes[i][0].value = self.values[slot]
            self.finalized = True
            _C_ASYNC_RESOLVED.inc()

    def replay_sync(self):
        """Resolve-rung recovery: re-execute the SAME captured chain
        synchronously — verbatim compile first, eager replay if that
        fails too — exactly the sync ladder minus the (already failed
        or unreachable) async rung. Bitwise-identical by the ladder
        contract. Idempotent under the finalize latch."""
        with self.flock:
            if not self.finalized:
                self.values = _exec_rungs(
                    self.nodes, self.leaves, self.consts, self.out_ixs,
                    self.cause, self.dtype, ladder=True,
                    use_passes=False)
                self.exc = None
                for slot, i in enumerate(self.out_ixs):
                    self.nodes[i][0].value = self.values[slot]
                self.finalized = True
                _C_ASYNC_RESOLVED.inc()
            return self.values


class ChainFuture:
    """Placeholder payload for one output slot of an async-flushed
    chain. Carries the declared shape/dtype so meta reads and further
    chain capture stay lazy; ``result()`` blocks on the worker."""

    __slots__ = ("sub", "slot", "shape", "dtype")

    def __init__(self, sub, slot, shape, dtype):
        self.sub = sub
        self.slot = slot
        self.shape = shape
        self.dtype = dtype

    def done(self):
        return self.sub.event.is_set()

    def result(self):
        """The concrete array: waits for the worker, re-raises its
        terminal failure, and finalizes the submission (stamps every
        sibling out Expr) on first success."""
        sub = self.sub
        sub.event.wait()
        if sub.exc is not None and not sub.finalized:
            raise sub.exc
        sub.finalize()
        return sub.values[self.slot]

    def __repr__(self):
        state = "done" if self.done() else "pending"
        return (f"ChainFuture(slot={self.slot}, shape={self.shape}, "
                f"{state})")


_ASYNC_COND = threading.Condition(threading.Lock())
_ASYNC_QUEUE: list = []
_ASYNC_INFLIGHT = 0
_ASYNC_THREAD = None


def _window():
    return max(1, int(flags_mod.flag("FLAGS_deferred_inflight")))


def _submit(sub, futures):
    """Publish the out futures and enqueue the submission ATOMICALLY
    (one critical section), then apply window backpressure AFTER the
    enqueue. The atomicity is what upholds the worker's FIFO
    materialization invariant across threads: another thread can only
    capture one of these futures as a leaf by reading an Expr value
    published here, and any submission it then makes takes this same
    lock — so it necessarily lands BEHIND ``sub`` in the queue, and
    the single worker materializes the dependency first. (Stamping
    before enqueue outside the lock would let a racing thread's
    dependent chain jump the queue while this submitter was parked on
    a full window — a worker deadlock.) Backpressure waits after the
    enqueue, so a parked submitter never blocks the worker; the
    in-flight count may transiently exceed the window by the parked
    submissions, which stays bounded by the number of capture
    threads."""
    global _ASYNC_THREAD, _ASYNC_INFLIGHT
    with _ASYNC_COND:
        if _ASYNC_THREAD is None or not _ASYNC_THREAD.is_alive():
            _ASYNC_THREAD = threading.Thread(
                target=_worker_loop, name="paddle-tpu-flush-worker",
                daemon=True)
            _ASYNC_THREAD.start()
        # nothing below this line may raise: the futures become
        # visible here, and an exception after publish would orphan
        # them (their event would never be set)
        for e, fut in futures:
            e.value = fut
        _ASYNC_INFLIGHT += 1
        _ASYNC_QUEUE.append(sub)
        _ASYNC_COND.notify_all()
        if _ASYNC_INFLIGHT > _window():
            _C_ASYNC_WINDOW_FULL.inc()
            while _ASYNC_INFLIGHT > _window():
                _ASYNC_COND.wait(0.5)


def _worker_loop():
    """The single flush worker: drains submissions FIFO (so a future
    used as a later chain's leaf is materialized before that chain
    runs) and executes each through the standard rung ladder inside a
    ``deferred.flush.async`` span stitched to the submitter's trace."""
    global _ASYNC_INFLIGHT
    while True:
        with _ASYNC_COND:
            while not _ASYNC_QUEUE:
                _ASYNC_COND.wait()
            sub = _ASYNC_QUEUE.pop(0)
        t0 = time.perf_counter_ns() if _prof.enabled else None
        try:
            _faults.site("deferred.async_exec")
            ladder = bool(flags_mod.flag("FLAGS_flush_degradation"))
            with _tracing.attach(sub.ctx):
                with _tracing.span("deferred.flush.async",
                                   cause=sub.cause,
                                   nodes=len(sub.nodes)):
                    rec = {}
                    sub.values = _exec_rungs(
                        sub.nodes, sub.leaves, sub.consts, sub.out_ixs,
                        sub.cause, sub.dtype, ladder,
                        passes_enabled(), rec)
            if t0 is not None and _prof.enabled:
                _prof.record("deferred_flush", t0 / 1000.0,
                             time.perf_counter_ns() / 1000.0, "Sync",
                             {"nodes": len(sub.nodes),
                              "cause": sub.cause, "async": True, **rec})
        except BaseException as e:  # noqa: BLE001 — surfaces at resolve
            sub.exc = e
        finally:
            sub.event.set()
            with _ASYNC_COND:
                _ASYNC_INFLIGHT -= 1
                _ASYNC_COND.notify_all()


def flush_async(root, cause="cap"):
    """Submit ``root``'s chain to the flush worker without blocking:
    every live-owned node is stamped with a :class:`ChainFuture` and
    capture continues. Returns root's new payload (a future, or the
    concrete value if the chain was already flushed, or — when the
    submit path itself fails and the degradation ladder is on — the
    synchronously computed array after a ``flush.async_submit``
    degrade)."""
    v = root.value
    if v is not None:
        return v
    nodes, leaves, consts = _linearize(root)
    _count_flush(cause, len(nodes))
    out_ixs = tuple(i for i, (e, _) in enumerate(nodes)
                    if e is root or (e.owner is not None
                                     and e.owner() is not None))
    sub = _Submission(nodes, leaves, consts, out_ixs, cause, root.dtype)
    futures = [(nodes[i][0], ChainFuture(sub, slot, nodes[i][0].shape,
                                         nodes[i][0].dtype))
               for slot, i in enumerate(out_ixs)]
    try:
        # the injection site fires BEFORE anything is published: a
        # submit failure leaves every Expr untouched (no orphaned
        # futures), and _submit publishes futures + enqueues in one
        # critical section (see its docstring for why)
        _faults.site("deferred.async_submit")
        _submit(sub, futures)
    except Exception as exc:  # noqa: BLE001 — async rung failure
        if not bool(flags_mod.flag("FLAGS_flush_degradation")):
            raise
        _resilience.degrade("flush.async_submit",
                            detail=f"nodes={len(nodes)} cause={cause}",
                            exc=exc)
        outs = _exec_rungs(nodes, leaves, consts, out_ixs, cause,
                           root.dtype, ladder=True, use_passes=False)
        for slot, i in enumerate(out_ixs):
            nodes[i][0].value = outs[slot]
        return root.value
    _C_ASYNC_SUBMIT.inc()
    return root.value


def _resolve_future_value(fut):
    """Host-side future resolution with the async degradation rung: a
    resolve failure (worker death, injected fault, a failed worker
    ladder) degrades to a synchronous replay of the SAME captured
    chain. Strict mode (`FLAGS_flush_degradation=0`) re-raises."""
    try:
        _faults.site("deferred.async_resolve")
        return fut.result()
    except Exception as exc:  # noqa: BLE001 — resolve rung
        if not bool(flags_mod.flag("FLAGS_flush_degradation")):
            raise
        _resilience.degrade(
            "flush.async_resolve",
            detail=f"nodes={len(fut.sub.nodes)} cause={fut.sub.cause}",
            exc=exc)
        return fut.sub.replay_sync()[fut.slot]


def _resolve_leaves(leaves):
    """Materialize any ChainFuture leaves (async-flushed upstream
    chains) before execution; recovery-aware, so a failed upstream
    submission replays synchronously right here."""
    if not any(type(v) is ChainFuture for v in leaves):
        return leaves
    return [_resolve_future_value(v) if type(v) is ChainFuture else v
            for v in leaves]


# -- flush ------------------------------------------------------------------

def flush(root):
    """Evaluate the chain as one jitted program. Every node still owned
    by a live Tensor is returned and stamped (shared subexpressions are
    never re-executed); returns the root's value. A root already
    stamped with an async ChainFuture resolves here — the lazy host
    read the async mode defers to.

    With ``FLAGS_deferred_passes`` on (default) the linearized chain
    runs through the paddle_tpu/passes pipeline (canonicalize, fold,
    CSE, then — under ``FLAGS_deferred_fusion`` — batch + fuse, then
    DCE) before cache lookup — smaller programs, canonical cache keys;
    ``PADDLE_TPU_PASSES=0`` keeps the verbatim capture-order compile.

    Degradation ladder (``FLAGS_flush_degradation``, default on): a
    failure never kills the step as long as the captured ops themselves
    are sound. Each rung re-executes the SAME captured chain, so every
    rung is bitwise-identical to the healthy path (chaos-gate pinned):

      rung A  async submit/exec/resolve failure -> synchronous
              verbatim recovery (``flush.async_submit`` /
              ``flush.async_resolve`` degrades), then rungs 1-2 below
      rung 0  pass pipeline + jit          (healthy)
      rung 1  any optimized-path failure   -> verbatim compile, the
              disjoint non-``passes/v*`` cache namespace
      rung 2  verbatim compile/run failure -> eager op-by-op replay,
              no jit at all (bitwise caveat: see the eager-replay rung)

    Rungs count ``resilience.degrade.flush.{retry_verbatim,
    eager_replay,async_submit,async_resolve}`` and append watchdog
    flight records. Ladder off = strict mode: the first exception
    propagates.

    The flush-counter label (data_read / op_boundary / cap) is the
    thread-local cause stamped by the triggering site via
    ``note_flush_cause``; it is consumed here and reset to the default
    ``data_read``."""
    v = root.value
    if v is not None:
        # already computed (a sibling flush, or an async submission):
        # nothing new runs, so discard any cause stamped for this read —
        # it must not leak onto the next real flush
        _take_cause()
        if type(v) is ChainFuture:
            return _resolve_future_value(v)
        return v
    cause = _take_cause()
    t0 = time.perf_counter_ns() if _prof.enabled else None
    nodes, leaves, consts = _linearize(root)
    _count_flush(cause, len(nodes))
    out_ixs = tuple(i for i, (e, _) in enumerate(nodes)
                    if e is root or (e.owner is not None
                                     and e.owner() is not None))
    ladder = bool(flags_mod.flag("FLAGS_flush_degradation"))
    # a child span when a request trace is active (serving prefill /
    # decode, an rpc handler) — the null path costs two no-op calls per
    # flush otherwise. Ladder rungs run INSIDE it, so a degraded flush
    # shows up as a long span with the degrade events stamped with the
    # same trace_id (resilience.degrade reads the ambient context).
    with _tracing.span("deferred.flush", cause=cause, nodes=len(nodes)):
        rec = {}
        outs = _exec_rungs(nodes, leaves, consts, out_ixs, cause,
                           root.dtype, ladder, passes_enabled(), rec)
        for slot, i in enumerate(out_ixs):
            nodes[i][0].value = outs[slot]
        if t0 is not None and _prof.enabled:
            _prof.record("deferred_flush", t0 / 1000.0,
                         time.perf_counter_ns() / 1000.0, "Sync",
                         {"nodes": len(nodes), "cause": cause, **rec})
    return root.value


def _exec_rungs(nodes, leaves, consts, out_ixs, cause, dtype, ladder,
                use_passes, rec=None):
    """The synchronous rung ladder over one captured chain: returns the
    out values ALIGNED WITH ``out_ixs`` (stamping is the caller's job —
    the async worker must not touch Expr values, host-side resolution
    does). Future leaves are materialized first, recovery-aware."""
    leaves = _resolve_leaves(leaves)
    if use_passes:
        try:
            return _exec_optimized(nodes, leaves, consts, out_ixs,
                                   dtype, rec)
        except Exception as e:  # noqa: BLE001 — rung 1 catches
            # anything the optimizer/compiler threw; sound-chain
            # errors re-raise from the rungs below
            if not ladder:
                raise
            _resilience.degrade(
                "flush.retry_verbatim",
                detail=f"nodes={len(nodes)} cause={cause}", exc=e)
    try:
        return _exec_verbatim(nodes, leaves, consts, out_ixs, dtype,
                              rec)
    except Exception as e:  # noqa: BLE001 — rung 2
        if not ladder:
            raise
        _resilience.degrade(
            "flush.eager_replay",
            detail=f"nodes={len(nodes)} cause={cause}", exc=e)
        return _exec_eager(nodes, leaves, consts, out_ixs, dtype, rec)


def _exec_verbatim(nodes, leaves, consts, out_ixs, dtype, rec=None):
    """Capture-order compile (no pass pipeline) — rung 0 when passes
    are disabled, rung 1 of the degradation ladder otherwise."""
    key = (tuple((e.node_key, spec) for e, spec in nodes), out_ixs)
    jf = _jit_cache_get(key)
    fresh = jf is None
    if fresh:
        jf = _maybe_aot_wrap(
            _build_chain_jf([(e.fn, spec, e.kwargs) for e, spec in nodes],
                            len(leaves), out_ixs),
            "deferred.verbatim")
        jf, fresh = _jit_cache_insert(key, jf)
    if not fresh:
        _C_JIT_HIT.inc()
    # consts ride as 0-d arrays AT THE CHAIN DTYPE — the same value a
    # weak python scalar would contribute against a dtype-uniform chain
    # (memoized: a 64-op chain has ~100 consts and flushes in a loop)
    cargs = [_const_arr(c, dtype) for c in consts]
    outs = _run_chain(jf, [*leaves, *cargs], fresh)
    if rec is not None:
        rec["compiled"] = fresh
    return list(outs)


def _exec_eager(nodes, leaves, consts, out_ixs, dtype, rec=None):
    """Rung 2: replay the captured chain op-by-op with NO jit — each fn
    is an ordinary jax op, dispatched eagerly in capture order over the
    same leaf/const arrays: exactly what ``FLAGS_eager_defer=0`` would
    have computed for the same user program. That equals the fused
    chain bitwise except where XLA contracts a mul->add pair into an
    FMA inside the fused program (see docs/ROBUSTNESS.md "fidelity
    caveat"; the chaos corpus pins contraction-exact chains). Survives
    compile-layer failures (RESOURCE_EXHAUSTED, cache corruption) at
    per-op dispatch cost."""
    cargs = [_const_arr(c, dtype) for c in consts]
    vals = _eval_chain([(e.fn, spec, e.kwargs) for e, spec in nodes],
                       leaves, cargs)
    _C_EAGER_REPLAY.inc()
    if rec is not None:
        rec["eager_replay"] = True
    return [vals[i] for i in out_ixs]


def _exec_optimized(nodes, leaves, consts, out_ixs, dtype, rec=None):
    """Pass-pipeline flush: linearized chain -> ir.Graph -> PassManager
    -> jit on the OPTIMIZED graph, keyed by its canonical structure
    (``passes/v2`` namespace when the fusion tier is on, ``passes/v1``
    for the cleanup-only pipeline — fused and unfused programs never
    collide).

    Outputs may have been rewritten to leaf/const references (a chain
    that canonicalized away entirely never compiles at all); node
    outputs come back from the single jitted call in order."""
    from ..passes import LEAF, NODE, Graph, default_manager

    _faults.site("deferred.passes")
    fusion = fusion_enabled()
    g = Graph.from_linearized(nodes, leaves, consts, out_ixs, dtype)
    g = default_manager(fusion=fusion).run(g)
    node_outs = tuple(ix for kind, ix in g.outputs if kind == NODE)
    fresh = False
    outs = ()
    if node_outs:
        key = ("passes/v2" if fusion else "passes/v1", g.cache_key())
        jf = _jit_cache_get(key)
        fresh = jf is None
        if fresh:
            jf = _maybe_aot_wrap(
                _build_chain_jf(
                    [(n.fn, n.args, n.kwargs) for n in g.nodes],
                    len(g.leaves), node_outs),
                f"deferred.{key[0]}")
            jf, fresh = _jit_cache_insert(key, jf)
        if not fresh:
            _C_JIT_HIT.inc()
        cargs = [_const_arr(c, dtype) for c in g.consts]
        outs = _run_chain(jf, [*g.leaves, *cargs], fresh)
    it = iter(outs)
    result = []
    for kind, ix in g.outputs:
        if kind == NODE:
            result.append(next(it))
        elif kind == LEAF:
            result.append(g.leaves[ix])
        else:  # const output: the same 0-d chain-dtype array the
            # in-graph computation would have produced
            result.append(_const_arr(g.consts[ix], dtype))
    if rec is not None:
        rec["compiled"] = fresh
        rec["opt_nodes"] = len(g.nodes)
    return result


_CONST_MEMO: dict = {}


def _const_arr(c, dtype):
    # repr distinguishes -0.0 from 0.0 (they hash equal as floats, but
    # x / -0.0 must stay -inf with the memo exactly as without it)
    key = (repr(c), str(dtype))
    a = _CONST_MEMO.get(key)
    if a is None:
        # build outside the lock — jnp.asarray is a device put, and the
        # lock is shared with _JIT_CACHE eviction on the flush path
        fresh = jnp.asarray(c, dtype=dtype)
        with _CACHE_LOCK:
            if len(_CONST_MEMO) > 4096:
                _CONST_MEMO.clear()
            a = _CONST_MEMO.setdefault(key, fresh)
    return a


def bind_owner(expr, tensor):
    """Record the Tensor owning this chain node (weakly): flush stamps
    values for nodes whose owners are still alive."""
    expr.owner = weakref.ref(tensor)


def release_owner(expr, tensor):
    """Inverse of bind_owner for payload replacement: ``tensor`` is
    adopting a new payload, so if it still owns ``expr`` the node's
    output can never be read through it — drop the owner weakref so
    later flushes of chains sharing the node don't compute it."""
    if expr is not None and expr.owner is not None \
            and expr.owner() is tensor:
        expr.owner = None
