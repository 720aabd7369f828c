"""Request-scoped tracing: always-on, sampled, bounded-overhead spans.

The metrics registry answers "how often / how slow on aggregate"; this
layer answers the question that follows every p99 spike: *which*
request, and where did its time go. A ``TraceContext`` (trace_id /
span_id) rides a ``contextvars.ContextVar`` through the code path that
serves one request; every instrumented slice (queue wait, prefill,
decode step, deferred flush, rpc dial, checkpoint write) records a
**span** — trace/span/parent ids, name, wall-clock start, duration,
thread, attrs — into a fixed-size per-process ring buffer.

Design rules (the ``testing/faults.py`` school):

- **Nearly free when disabled.** Every entry point gates on ONE module
  global (refreshed only when the flags epoch moves); a disabled
  ``span()`` is a flag read returning a preallocated null object.
- **Sampled at the root.** The sampling decision is made once per
  trace, at ``start_trace`` (``FLAGS_trace_sample`` fraction of
  requests); children of an unsampled root cost the same null path as
  disabled tracing, so steady-state overhead scales with the sample
  rate, not the traffic.
- **Bounded memory.** Spans land in a ring of ``FLAGS_trace_ring``
  slots; old traces age out instead of growing the host heap. Exports
  (`export_trace` / `export_ring`) render Chrome/Perfetto trace-event
  JSON from whatever the ring still holds.

Wire propagation: ``current_context()`` returns a small picklable dict
and ``attach(ctx)`` adopts it, so ``distributed/rpc.py`` can carry the
context across hosts — spans recorded on every host share one
trace_id and stitch into a single trace at export time.

Usage::

    from paddle_tpu.profiler import tracing

    root = tracing.start_trace("serving.request", rid=7)   # samples
    with tracing.span("prefill", parent=root, tokens=128):
        ...                                # nested spans auto-parent
    root.end("DONE")

    tracing.export_trace(root.trace_id)    # {"traceEvents": [...]}

The span catalog lives in docs/OBSERVABILITY.md; histograms link back
here via exemplars (profiler/metrics.py) and the /metrics endpoint
(profiler/export.py) serves ``/traces/<id>``.

**Phase spans** are the other axis: a slice of the *engine's thread*
(one scheduler step, its admission, its decode dispatch), not of a
request. ``phase(name)`` is always on and unsampled: it enters a
``jax.profiler.TraceAnnotation``, so while a profiler session runs the
slice lands on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the
device's operations, on the same clock (``benchmarks/span_reduce.py``
reads idle-gap owners from it), and on exit it adds its elapsed time to
the registry histogram ``serving.phase.<name>_us``. The request spans
above carry the ``step`` of the ``serving.step`` phase that ran them.

**Scopes** name the inside of a compiled program. ``scope(component)``
enters ``jax.named_scope("pt.<component>")`` for one of ``SCOPE_NAMES``:
every operation traced inside carries the path in its ``op_name``
(``jit(llama_paged_decode)/layers.3/pt.attn/self_attn/o_proj/
dot_general``), which a profiler trace shows beside the operation and
``benchmarks/scope_reduce.py`` splits the device's busy time by. A
scope is metadata of the traced operations: it exists while a program
is traced, and the compiled program is the same with or without it.
The detail beneath a component is ``Layer.__call__``'s, which enters
the name a layer was registered under in its parent.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import random
import threading
import time

from jax import named_scope as _named_scope
from jax.profiler import TraceAnnotation as _TraceAnnotation

from ..core import flags as flags_mod
from . import metrics as _metrics

__all__ = ["Span", "start_trace", "span", "record_span", "phase",
           "PHASE_NAMES", "BLOCK_PHASE_NAMES", "phase_histogram_name",
           "scope", "SCOPE_NAMES", "SCOPE_MARK", "attach",
           "current_context", "current_trace_id", "get_trace",
           "trace_ids", "export_trace", "export_ring", "records",
           "enabled", "reset"]

# (trace_id, span_id) of the innermost active span on this
# thread/task; None = no sampled trace active
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "paddle_tpu_trace", default=None)

_C_TRACES = _metrics.counter("trace.traces")
_C_SPANS = _metrics.counter("trace.spans")
_C_UNSAMPLED = _metrics.counter("trace.unsampled")


class _Ring:
    """Fixed-size span store: append overwrites the oldest slot. The
    lock guards only an index bump + one slot write (~same cost as a
    Counter.inc)."""

    __slots__ = ("cap", "_buf", "_n", "_lock")

    def __init__(self, cap):
        self.cap = max(int(cap), 1)
        self._buf = [None] * self.cap
        self._n = 0
        self._lock = threading.Lock()

    def append(self, rec):
        with self._lock:
            self._buf[self._n % self.cap] = rec
            self._n += 1

    def records(self):
        with self._lock:
            n, cap = self._n, self.cap
            if n <= cap:
                return list(self._buf[:n])
            i = n % cap
            return self._buf[i:] + self._buf[:i]

    def clear(self):
        with self._lock:
            self._buf = [None] * self.cap
            self._n = 0


# the disabled-path contract: span()/start_trace() read _ENABLED (one
# module global) after a one-int epoch compare; everything else is
# refreshed only when core.flags mutates
_ENABLED = True
_SAMPLE = 1.0
_EPOCH_SEEN = -1
_ring = _Ring(4096)
_refresh_lock = threading.Lock()


def _gate():
    if flags_mod.epoch() != _EPOCH_SEEN:
        _refresh()
    return _ENABLED


def _refresh():
    global _ENABLED, _SAMPLE, _EPOCH_SEEN, _ring
    with _refresh_lock:
        ep = flags_mod.epoch()
        sample = float(flags_mod.flag("FLAGS_trace_sample"))
        cap = int(flags_mod.flag("FLAGS_trace_ring"))
        if cap > 0 and cap != _ring.cap:
            _ring = _Ring(cap)  # resize drops history (rare, ops-only)
        _SAMPLE = sample
        _ENABLED = bool(flags_mod.flag("FLAGS_trace_enable")) \
            and sample > 0.0
        _EPOCH_SEEN = ep


def enabled():
    """True iff tracing is armed (flag on and sample rate > 0)."""
    return _gate()


# private RNG (urandom-seeded): user random.seed(k) — typically the
# SAME k on every host of a reproducible distributed launch — must not
# make hosts mint colliding trace ids or correlated sampling decisions,
# and tracing must not consume draws from the user's seeded stream
_rng = random.Random()


def _new_id():
    return f"{_rng.getrandbits(64):016x}"


class _NullSpan:
    """Preallocated no-op span: what every entry point returns when
    tracing is disabled or the trace was not sampled."""

    __slots__ = ()
    trace_id = None
    span_id = None
    recording = False

    def annotate(self, **attrs):
        pass

    def end(self, status="ok"):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False


NULL = _NullSpan()


def _record(trace_id, span_id, parent_id, name, dur_us, status, args):
    """Put one slice that ends NOW into the ring. The one place a ring
    record is stamped: ``dur`` comes from the caller's monotonic clock
    and ``ts`` is the wall clock now less ``dur``, whether the slice was
    timed live (``Span.end``) or handed over after the fact
    (``record_span``)."""
    rec = {"trace": trace_id, "span": span_id, "parent": parent_id,
           "name": name, "ts": time.time_ns() / 1000.0 - dur_us,
           "dur": float(dur_us), "tid": threading.get_ident(),
           "status": status}
    if args:
        rec["args"] = args
    _ring.append(rec)
    _C_SPANS.inc()


class Span:
    """One recorded slice. Use as a context manager (sets the ambient
    context so nested spans auto-parent) or hold it and call ``end()``
    manually — the serving root span lives from submit to terminal
    status across threads, so it is held on the request."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "args",
                 "_start_ns", "_ended", "_token")

    recording = True

    def __init__(self, trace_id, span_id, parent_id, name, args):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.args = args
        self._start_ns = time.perf_counter_ns()
        self._ended = False
        self._token = None

    def annotate(self, **attrs):
        """Attach attrs to the span (merged into args at record time)."""
        if self.args:
            self.args.update(attrs)
        else:
            self.args = attrs

    def end(self, status="ok"):
        """Record the span into the ring. Idempotent; ``status`` is a
        free-form label ("ok", "error", a terminal request status)."""
        if self._ended:
            return
        self._ended = True
        _record(self.trace_id, self.span_id, self.parent_id, self.name,
                (time.perf_counter_ns() - self._start_ns) / 1000.0,
                status, self.args)

    def context(self):
        """Picklable propagation dict (rpc wire / cross-thread)."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def __enter__(self):
        self._token = _CURRENT.set((self.trace_id, self.span_id))
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._token is not None:
            _CURRENT.reset(self._token)
            self._token = None
        self.end("ok" if exc_type is None else "error")
        return False


def start_trace(name, **attrs):
    """Open a ROOT span: mints a fresh trace_id and applies the
    sampling decision. Returns the null span when tracing is off or
    the trace lost the sample draw — children of an unsampled root
    no-op for free. Does NOT set the ambient context (roots are held
    across threads); use it as a ``with`` block or pass it as
    ``parent=`` explicitly."""
    if not _gate():
        return NULL
    if _SAMPLE < 1.0 and _rng.random() >= _SAMPLE:
        _C_UNSAMPLED.inc()
        return NULL
    _C_TRACES.inc()
    return Span(_new_id(), _new_id(), None, name, attrs or None)


def span(name, parent=None, **attrs):
    """Open a child span. Parent resolution: an explicit ``parent``
    (a Span or a propagation dict), else the ambient context; no
    parent anywhere -> the null span (a slice outside any trace is
    never recorded — that is what keeps disabled overhead flat)."""
    if not _gate():
        return NULL
    if parent is None:
        cur = _CURRENT.get()
        if cur is None:
            return NULL
        tid, psid = cur
    elif isinstance(parent, Span):
        tid, psid = parent.trace_id, parent.span_id
    elif isinstance(parent, dict):
        tid = parent.get("trace_id")
        if tid is None:
            return NULL
        psid = parent.get("span_id")
    else:  # NULL or anything non-recording
        return NULL
    return Span(tid, _new_id(), psid, name, attrs or None)


def record_span(name, parent, dur_us, **attrs):
    """Record a RETROACTIVE slice of ``dur_us`` ending now, under
    ``parent`` (a Span). Used where the duration is known only after
    the fact — queue wait, the per-request share of a batched decode
    step. No-op unless the parent is recording."""
    if not getattr(parent, "recording", False) or not _gate():
        return
    _record(parent.trace_id, _new_id(), parent.span_id, name, dur_us,
            "ok", attrs)


# -- phase spans: slices of the engine's thread ------------------------------

# the names are the contract (docs/OBSERVABILITY.md "Phase spans";
# benchmarks/span_reduce.py groups idle gaps by them)
PHASE_NAMES = (
    "serving.engine.no_work", "serving.engine.lock_wait",
    "serving.step", "serving.sweep", "serving.overload",
    "serving.admit", "serving.admit.plan", "serving.admit.finish",
    "serving.prefill.forward", "serving.prefill.pool_write",
    "serving.prefill.readback",
    "serving.decode", "serving.decode.prepare", "serving.decode.dispatch",
    "serving.decode.readback", "serving.decode.emit", "serving.step_end")
# inside serving.decode.emit of a block-diffusion model's step only
# (``Scheduler._emit_blocks``): no other model feeds it
BLOCK_PHASE_NAMES = ("serving.block.commit",)


def phase_histogram_name(name):
    """``serving.decode.dispatch`` -> ``serving.phase.decode_dispatch_us``."""
    return "serving.phase." + \
        name.removeprefix("serving.").replace(".", "_") + "_us"


# 1-2-5 from 10 us to 10 s: a phase is anything from a dict lookup to a
# cold compile
_PHASE_BOUNDS = tuple(m * 10 ** e for e in range(1, 7) for m in (1, 2, 5)) \
    + (10 ** 7,)
# created once, here, like every module's histograms; ``serving.step``
# has none of its own: the scheduler feeds ``serving.step_us`` as it
# always did
_PHASE_HIST = {n: _metrics.histogram(phase_histogram_name(n),
                                     bounds=_PHASE_BOUNDS)
               for n in PHASE_NAMES + BLOCK_PHASE_NAMES
               if n != "serving.step"}
_PHASE_HIST["serving.step"] = None


class phase:  # noqa: N801 — used as a function: ``with phase(name):``
    """A slice of the engine's thread. Always on, never sampled: two
    clock reads, one histogram ``observe`` and one ``TraceMe`` (while no
    profiler session runs, its flag test alone). ``attrs`` become the
    event's stats in the profiler's trace. Phases nest; a phase's self
    time is its time less its children's, which the reducer of the
    trace computes, not the program."""

    __slots__ = ("_ann", "_hist", "_t0")

    def __init__(self, name, **attrs):
        self._ann = _TraceAnnotation(name, **attrs) \
            if _TraceAnnotation.is_enabled() else None
        self._hist = _PHASE_HIST[name]  # KeyError: not a catalogued name

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur_us = (time.perf_counter_ns() - self._t0) / 1000.0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self._hist is not None:
            self._hist.observe(dur_us)
        return False


# -- scopes: the parts of a model inside a compiled program -------------------

# the names are the contract (docs/OBSERVABILITY.md "Scopes inside a
# program"; benchmarks/scope_reduce.py splits busy time by them)
SCOPE_NAMES = ("attn", "ffn", "mixer", "residual", "head", "optimizer")
# what a component's segment of an ``op_name`` starts with: no layer is
# registered under a name with a dot in it, so a reader cannot mistake
# an attribute for a component
SCOPE_MARK = "pt."
_SCOPE_SEGMENT = {n: SCOPE_MARK + n for n in SCOPE_NAMES}


def scope(component):
    """Name what is traced inside the block as part of ``component``
    (one of ``SCOPE_NAMES``): the name-stack entry ``pt.<component>``
    and nothing else. Where scopes nest, the innermost component owns
    the operation."""
    return _named_scope(_SCOPE_SEGMENT[component])  # KeyError: not catalogued


@contextlib.contextmanager
def attach(ctx):
    """Adopt a propagated context for the duration of the block: the
    rpc server wraps remote-fn execution so multi-host spans stitch
    into the caller's trace, and the scheduler wraps per-request SLO
    observations so histogram exemplars capture the right trace_id.
    ``ctx`` is a Span, a ``current_context()`` dict, or None (no-op)."""
    if ctx is None or not _gate():
        yield
        return
    if isinstance(ctx, Span):
        pair = (ctx.trace_id, ctx.span_id)
    elif isinstance(ctx, dict) and ctx.get("trace_id"):
        pair = (ctx["trace_id"], ctx.get("span_id"))
    else:
        yield
        return
    token = _CURRENT.set(pair)
    try:
        yield
    finally:
        _CURRENT.reset(token)


def current_context():
    """Propagation dict for the ambient context, or None. Picklable —
    this is what rides the rpc wire."""
    cur = _CURRENT.get()
    if cur is None:
        return None
    return {"trace_id": cur[0], "span_id": cur[1]}


def current_trace_id():
    """The ambient trace_id or None — the exemplar source for
    profiler.metrics histograms (installed below) and the stamp on
    resilience/watchdog flight records."""
    cur = _CURRENT.get()
    return cur[0] if cur is not None else None


# -- reading the ring ------------------------------------------------------

def records():
    """Every span still in the ring, oldest first."""
    return [r for r in _ring.records() if r is not None]


def get_trace(trace_id):
    """All ring spans of one trace, by start time. A long-lived trace
    may have aged out partially — callers that need completeness
    export promptly (the /traces endpoint) or raise FLAGS_trace_ring."""
    return sorted((r for r in records() if r["trace"] == trace_id),
                  key=lambda r: r["ts"])


def trace_ids():
    """Distinct trace ids currently in the ring (most recent last)."""
    out, seen = [], set()
    for r in records():
        if r["trace"] not in seen:
            seen.add(r["trace"])
            out.append(r["trace"])
    return out


def _chrome_event(r):
    ev = {"name": r["name"], "ph": "X", "ts": r["ts"], "dur": r["dur"],
          "pid": os.getpid(), "tid": r["tid"], "cat": "trace",
          "args": {"trace_id": r["trace"], "span_id": r["span"],
                   "parent_id": r["parent"], "status": r["status"]}}
    if r.get("args"):
        ev["args"].update(r["args"])
    return ev


def export_trace(trace_id):
    """One trace as Chrome/Perfetto trace-event JSON (a plain dict —
    ``json.dump`` it, or serve it via the /traces/<id> endpoint)."""
    return {"traceEvents": [_chrome_event(r) for r in
                            get_trace(trace_id)],
            "displayTimeUnit": "ms", "trace_id": trace_id}


def export_ring():
    """The whole ring as one Chrome/Perfetto trace-event JSON dict —
    the post-mortem dump (every recent trace interleaved)."""
    return {"traceEvents": [_chrome_event(r) for r in records()],
            "displayTimeUnit": "ms"}


def reset():
    """Clear the ring (tests / between benchmark runs)."""
    _ring.clear()


# histograms capture the ambient trace_id as a bucket exemplar — wire
# the probe here so metrics.py never imports tracing (no cycle)
_metrics._set_trace_id_source(current_trace_id)
