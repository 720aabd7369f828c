"""Always-on runtime metrics registry.

The reference ships a profiler that must be armed to see anything; the
questions that actually come up in production ("is the lazy-vjp cache
hitting", "how often do deferred chains flush", "is jit recompiling every
step") need counters that are ALWAYS live, cost ~a dict hit + int add per
event, and can be snapshotted at any moment without pausing the program.

Three instrument kinds, Prometheus-shaped:

- ``Counter``   — monotone event count (``inc``)
- ``Gauge``     — last-write-wins level (``set`` / ``add``)
- ``Histogram`` — value distribution (``observe``): count / sum / min /
  max plus fixed-bound bucket counts

All mutation is lock-guarded (instrumented code runs from worker threads
— e.g. DataLoader workers dispatching ops), and ``snapshot()`` returns a
deep copy so a reader never observes later mutation.

Usage::

    from paddle_tpu.profiler import metrics
    metrics.counter("my.events").inc()
    metrics.histogram("my.latency_us").observe(dt)
    print(metrics.dump())          # human-readable table
    metrics.snapshot()             # {name: value | dict} plain data

XLA compile telemetry rides on ``jax.monitoring``: importing this module
subscribes a listener that folds ``/jax/core/compile/*`` durations into
``xla.compile.count`` / ``xla.compile.seconds`` — every backend compile
is counted no matter which layer (deferred chains, lazy-vjp jits, user
``jax.jit``) triggered it.
"""

from __future__ import annotations

import bisect
import json
import os
import socket
import threading
import time

__all__ = ["Counter", "Gauge", "Histogram", "counter", "gauge",
           "histogram", "snapshot", "dump", "reset", "registry",
           "thread_compile_seconds", "replica_identity",
           "set_replica_id", "label_key", "Window", "window_delta",
           "cumulative_buckets", "percentile_from_buckets"]


def _esc_label_value(v):
    """Label-value escaping per the exposition format (backslash,
    double quote, newline). The canonical implementation lives here —
    ``profiler.export`` aliases it (export depends on this module, so
    the reverse import would cycle)."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _label_body(labels):
    """Sorted-key, escaped ``k="v",...`` body of a label block — the
    one canonical form shared by :func:`label_key` and the exposition
    renderer (``profiler.export._labelblock``)."""
    return ",".join(f'{k}="{_esc_label_value(v)}"'
                    for k, v in sorted(labels.items()))


def label_key(name, labels):
    """Canonical registry key for a labeled series:
    ``name{k="v",...}`` with sorted keys and escaped values — the same
    label-block canonicalization ``profiler.export`` renders and
    parses (modulo its dot->underscore metric-name mangling), so a
    labeled gauge round-trips through a scrape with its labels
    intact."""
    if not labels:
        return name
    return name + "{" + _label_body(labels) + "}"


# -- replica identity ------------------------------------------------------
# once more than one serving process exists, a metrics dump or a scrape
# is meaningless without knowing WHICH replica produced it. The identity
# is process-scoped (the registry is process-global); fleet registration
# (profiler/fleet.py) reuses it and may override replica_id per
# registration when several replicas share a process (tests, gates).

_START_TS = time.time()
try:
    _HOST = socket.gethostname()
except Exception:  # noqa: BLE001 — identity must never break import
    _HOST = "localhost"
_replica_id = None
_identity_lock = threading.Lock()


def set_replica_id(replica_id):
    """Override the process replica id (None restores the default
    ``<host>-<pid>``). Fleet registration (profiler/fleet.Registrar)
    adopts its replica_id here when nothing set one yet, so the
    ``replica_info`` series and ``dump()`` envelope agree with the
    registry name in the one-replica-per-process case."""
    global _replica_id
    with _identity_lock:
        _replica_id = str(replica_id) if replica_id is not None else None


def replica_id_overridden():
    """True iff an explicit replica id is set (vs the host-pid
    default) — fleet registration only adopts its name when not."""
    with _identity_lock:
        return _replica_id is not None


def replica_identity():
    """This process's replica identity: ``{replica_id, host, pid,
    start_ts}`` — stamped into ``dump()``'s JSON envelope and exported
    as the ``replica_info`` OpenMetrics series (profiler/export.py), so
    ledger entries and scrapes stay attributable across a fleet."""
    with _identity_lock:
        rid = _replica_id
    pid = os.getpid()
    return {"replica_id": rid if rid is not None else f"{_HOST}-{pid}",
            "host": _HOST, "pid": pid, "start_ts": _START_TS}


# -- histogram exemplars ---------------------------------------------------
# profiler.tracing installs the ambient-trace probe at import; until
# then (or with tracing disabled) observations pay one call returning
# None. Keeping the hook here (instead of importing tracing) avoids an
# import cycle: tracing needs counters from this module.

def _no_trace():
    return None


_trace_id_fn = _no_trace


def _set_trace_id_source(fn):
    global _trace_id_fn
    _trace_id_fn = fn


class Counter:
    """Monotonically increasing event count."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n=1):
        with self._lock:
            self._value += n

    @property
    def value(self):
        return self._value

    def _snap(self):
        return self._value

    def _reset(self):
        with self._lock:
            self._value = 0


class Gauge:
    """Last-write-wins level (cache sizes, live bytes, ...).

    ``labels`` (optional, a flat str dict) makes this a LABELED series:
    the registry keys it as ``name{k="v",...}`` (the exposition-format
    key ``profiler.export.parse_prometheus`` produces), the exporter
    renders the label block on the sample line, and fleet federation
    treats it like a replica-labeled series — per-origin by definition,
    never summed into a fleet aggregate. The mesh-sharded serving
    layer's per-slice KV gauges (``serving.kv.*{slice="i"}``) are the
    first user (docs/OBSERVABILITY.md)."""

    __slots__ = ("name", "labels", "_value", "_lock")

    def __init__(self, name, labels=None):
        self.name = name
        self.labels = dict(labels) if labels else None
        self._value = 0
        self._lock = threading.Lock()

    def set(self, v):
        with self._lock:
            self._value = v

    def add(self, n=1):
        with self._lock:
            self._value += n

    @property
    def value(self):
        return self._value

    def _snap(self):
        return self._value

    def _reset(self):
        with self._lock:
            self._value = 0


# default bounds suit the two native uses: chain lengths (1..64) and
# microsecond-scale latencies — override per-histogram at creation
_DEFAULT_BOUNDS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)


class Histogram:
    """Fixed-bucket distribution: bucket[i] counts observations
    <= bounds[i]; one overflow bucket catches the rest.

    Each bucket retains one **exemplar** — the max-value observation
    seen while a trace was active, with its trace_id and wall time —
    so an SLO histogram (``serving.ttft_us``) points at an exportable
    trace for exactly the sample that defined its tail
    (profiler/tracing.py; rendered as OpenMetrics exemplars by
    profiler/export.py)."""

    __slots__ = ("name", "bounds", "_buckets", "_count", "_sum", "_min",
                 "_max", "_exemplars", "_lock")

    def __init__(self, name, bounds=_DEFAULT_BOUNDS):
        self.name = name
        self.bounds = tuple(sorted(bounds))
        self._buckets = [0] * (len(self.bounds) + 1)
        self._count = 0
        self._sum = 0.0
        self._min = None
        self._max = None
        self._exemplars = [None] * (len(self.bounds) + 1)
        self._lock = threading.Lock()

    def observe(self, v):
        tid = _trace_id_fn()
        i = bisect.bisect_left(self.bounds, v)  # first bound >= v
        with self._lock:
            self._buckets[i] += 1
            self._count += 1
            self._sum += v
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v
            if tid is not None:
                ex = self._exemplars[i]
                if ex is None or v >= ex[0]:
                    self._exemplars[i] = (v, tid, time.time())

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def percentile(self, q):
        """Estimate the q-quantile (0..1) from bucket counts: linear
        interpolation inside the covering bucket, edge buckets clamped
        to the observed min/max. Exact at the bucket bounds; off by at
        most one bucket width inside — good enough to see a tail move
        without hand math over the bucket table."""
        with self._lock:
            return self._pct_locked(q)

    def _pct_locked(self, q):
        if not self._count:
            return None
        target = q * self._count
        cum = 0
        for i, n in enumerate(self._buckets):
            if not n:
                continue
            # interpolate inside THIS bucket's own bounds (clamped to
            # the observed range) — the previous non-empty bucket's
            # upper edge is not a valid floor across empty buckets
            lo = self.bounds[i - 1] if i > 0 else self._min
            hi = self.bounds[i] if i < len(self.bounds) else self._max
            lo = min(max(lo, self._min), self._max)
            hi = min(max(hi, lo), self._max)
            if cum + n >= target:
                frac = (target - cum) / n
                return lo + (hi - lo) * frac
            cum += n
        return self._max

    def _snap(self):
        with self._lock:
            labels = [*map(str, self.bounds), "+inf"]
            exemplars = {
                labels[i]: {"value": ex[0], "trace_id": ex[1],
                            "ts": ex[2]}
                for i, ex in enumerate(self._exemplars)
                if ex is not None}
            return {"count": self._count, "sum": self._sum,
                    "min": self._min, "max": self._max,
                    "avg": (self._sum / self._count) if self._count else None,
                    "p50": self._pct_locked(0.50),
                    "p95": self._pct_locked(0.95),
                    "p99": self._pct_locked(0.99),
                    "buckets": dict(zip(labels, list(self._buckets))),
                    "exemplars": exemplars}

    def _reset(self):
        with self._lock:
            self._buckets = [0] * (len(self.bounds) + 1)
            self._count = 0
            self._sum = 0.0
            self._min = None
            self._max = None
            self._exemplars = [None] * (len(self.bounds) + 1)


class Registry:
    """Name -> instrument. Get-or-create is locked; the returned objects
    are cached at call sites so steady-state cost is one ``inc``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}
        self._dump_seq = 0

    def _get(self, name, cls, **kw):
        m = self._metrics.get(name)
        if m is None:
            with self._lock:
                m = self._metrics.get(name)
                if m is None:
                    m = self._metrics[name] = cls(name, **kw)
        if not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(m).__name__}, not {cls.__name__}")
        return m

    def counter(self, name):
        return self._get(name, Counter)

    def gauge(self, name, labels=None):
        """Get-or-create a gauge; ``labels`` (flat str dict) registers
        a LABELED series keyed ``name{k="v",...}`` — the canonical form
        ``profiler.export`` renders and parses, so a snapshot/scrape of
        a labeled gauge round-trips with its labels intact. The
        instrument's ``.name`` stays the BASE name; only the registry
        key carries the label block."""
        if not labels:
            return self._get(name, Gauge)
        key = label_key(name, labels)
        m = self._metrics.get(key)
        if m is None:
            with self._lock:
                m = self._metrics.get(key)
                if m is None:
                    m = self._metrics[key] = Gauge(name, labels=labels)
        if not isinstance(m, Gauge):
            raise TypeError(
                f"metric {key!r} already registered as "
                f"{type(m).__name__}, not Gauge")
        return m

    def histogram(self, name, bounds=_DEFAULT_BOUNDS):
        return self._get(name, Histogram, bounds=bounds)

    def snapshot(self, prefix=None):
        """Plain-data copy of every metric, isolated from later updates.
        ``prefix`` restricts to one metric family (``"passes."``,
        ``"deferred."``, ...) — what gates and tests diff against."""
        with self._lock:
            items = list(self._metrics.items())
        return {name: m._snap() for name, m in items
                if prefix is None or name.startswith(prefix)}

    def kinds(self, prefix=None):
        """{name: instrument class} for registered metrics — the public
        way for consumers (export.DeltaRates) to tell counters from
        gauges without reaching into registry internals."""
        with self._lock:
            return {name: type(m) for name, m in self._metrics.items()
                    if prefix is None or name.startswith(prefix)}

    def dump(self, path=None, prefix=None):
        """Human-readable table; optionally also written to ``path`` as
        JSON for machine consumption. The JSON envelope carries a
        wall-clock ``ts``, a process-monotone ``seq``, and the process
        ``replica`` identity (:func:`replica_identity`) so successive
        dumps from a gate or watcher diff/order cleanly AND stay
        attributable once more than one process exists; the metric map
        itself sits under ``"metrics"``."""
        snap = self.snapshot(prefix)
        lines = ["{:<48} {}".format("metric", "value")]
        for name in sorted(snap):
            v = snap[name]
            if isinstance(v, dict):
                desc = (f"count={v['count']} sum={v['sum']:.6g}"
                        + (f" avg={v['avg']:.6g} min={v['min']:.6g}"
                           f" max={v['max']:.6g} p50={v['p50']:.6g}"
                           f" p95={v['p95']:.6g} p99={v['p99']:.6g}"
                           if v["count"] else ""))
            else:
                desc = str(v)
            lines.append("{:<48} {}".format(name, desc))
        text = "\n".join(lines)
        if path is not None:
            with self._lock:
                self._dump_seq += 1
                seq = self._dump_seq
            with open(path, "w") as f:
                json.dump({"ts": time.time(), "seq": seq,
                           "replica": replica_identity(),
                           "metrics": snap}, f, indent=1, sort_keys=True)
        return text

    def reset(self):
        """Zero every registered metric (tests / between benchmark runs).
        Instrument objects stay valid: call sites keep cached references."""
        with self._lock:
            items = list(self._metrics.values())
        for m in items:
            m._reset()


registry = Registry()

counter = registry.counter
gauge = registry.gauge
histogram = registry.histogram
snapshot = registry.snapshot
dump = registry.dump
reset = registry.reset


# -- scenario-scoped measurement: Window over the always-on registry -------
# The registry is process-global and always on; a load scenario that
# wants "TTFT p95 during THIS burst phase" must not reset() it (other
# phases, gates, and the exporter read the same counters). A Window is
# a snapshot-diff: open it at phase start, freeze it at phase end, and
# every read sees exactly the slice of activity between the two — the
# measurement primitive profiler/scorecard.py and the fleet-load gate
# are built on (docs/OBSERVABILITY.md "Scenario observatory").


def _le_sort_key(le):
    """Numeric sort key for a bucket's ``le`` label. Canonical home —
    ``profiler.export`` and ``profiler.fleet`` alias this (both depend
    on this module, so the reverse import would cycle)."""
    return float("inf") if le in ("+Inf", "+inf") else float(le)


def cumulative_buckets(buckets):
    """Per-bucket ``{le: count}`` (the snapshot form) to CUMULATIVE
    ``{le: cum_count}`` (the exposition/merged form
    :func:`percentile_from_buckets` consumes), ordered by bound."""
    items = sorted((_le_sort_key(le), le, c)
                   for le, c in (buckets or {}).items())
    out, cum = {}, 0
    for _, le, c in items:
        cum += c
        out[le] = cum
    return out


def percentile_from_buckets(buckets, q):
    """q-quantile (0..1) from a CUMULATIVE bucket map ``{le_label:
    cumulative_count}`` (the exposition/merged form): linear
    interpolation inside the covering bucket, 0-floored (an exposition
    carries no observed min) and clamped to the last finite bound for
    the +inf bucket. None on an empty histogram. Pure — fleet SLO
    percentiles, the skew rule, and Window percentiles are
    deterministic on fixed bucket maps. (Hoisted from profiler/fleet.py
    — the ONE bucket-interpolation implementation; fleet re-exports
    it.)"""
    items = sorted((_le_sort_key(le), c)
                   for le, c in (buckets or {}).items())
    if not items:
        return None
    total = items[-1][1]
    if not total:
        return None
    target = q * total
    prev_bound, prev_cum, last_finite = 0.0, 0, 0.0
    for bound, cum in items:
        finite = bound != float("inf")
        if cum >= target:
            n = cum - prev_cum
            frac = (target - prev_cum) / n if n else 1.0
            hi = bound if finite else max(prev_bound, last_finite)
            return prev_bound + (hi - prev_bound) * frac
        if finite:
            last_finite = bound
        prev_bound, prev_cum = (bound if finite else prev_bound), cum
    return last_finite


def _hist_delta(cur, prev):
    """Windowed slice of one histogram snapshot dict. Buckets/count/sum
    are exact diffs (closure: window + pre-window == total, bucket by
    bucket); min/max are not recoverable from two snapshots so the
    delta reports the window's bucket-derived percentiles instead and
    leaves min/max None. A reset() between the snapshots makes a diff
    go negative — the window then treats ``cur`` as a fresh start."""
    pb = prev.get("buckets") if isinstance(prev, dict) else None
    buckets = {le: c - (pb.get(le, 0) if pb else 0)
               for le, c in cur["buckets"].items()}
    count = cur["count"] - (prev["count"] if isinstance(prev, dict) else 0)
    total = cur["sum"] - (prev["sum"] if isinstance(prev, dict) else 0)
    if count < 0 or any(v < 0 for v in buckets.values()):
        buckets = dict(cur["buckets"])
        count, total = cur["count"], cur["sum"]
    cum = cumulative_buckets(buckets)
    return {"count": count, "sum": total,
            "avg": (total / count) if count else None,
            "min": None, "max": None,
            "p50": percentile_from_buckets(cum, 0.50),
            "p95": percentile_from_buckets(cum, 0.95),
            "p99": percentile_from_buckets(cum, 0.99),
            "buckets": buckets}


def window_delta(before, after):
    """Pure snapshot diff ``after - before`` over two :func:`snapshot`
    maps: scalars (counters AND gauges) become numeric deltas,
    histograms become windowed dicts (:func:`_hist_delta` — bucket-wise
    diffs plus window percentiles). Metrics born after ``before`` diff
    against zero. Scalar deltas are SIGNED (gauges legitimately fall;
    a counter going negative means a reset() landed between the
    snapshots — the one case where closure cannot hold, because data
    was destroyed). Exemplars are point-in-time, not diffable, and are
    dropped."""
    out = {}
    for name, cur in after.items():
        prev = before.get(name)
        if isinstance(cur, dict):
            out[name] = _hist_delta(cur, prev)
        else:
            prev_v = prev if isinstance(prev, (int, float)) else 0
            out[name] = cur - prev_v
    return out


class Window:
    """Scenario-scoped view of the registry: ``Window(prefix)`` pins a
    base snapshot; :meth:`freeze` pins the end; every read diffs the
    two (or diffs live against the base while unfrozen). Global state
    is never reset — any number of overlapping windows observe the
    same registry, each seeing exactly its own slice.

        w = metrics.Window("serving.")
        ... drive one scenario phase ...
        w.freeze()
        w.value("serving.admitted")            # counter delta
        w.percentile("serving.ttft_us", 0.95)  # windowed p95
    """

    def __init__(self, prefix=None, label=None):
        self.prefix = prefix
        self.label = label
        self.start_ts = time.time()
        self.end_ts = None
        self._base = registry.snapshot(prefix)
        self._end = None

    def freeze(self):
        """Pin the window's end snapshot (idempotent); reads stop
        tracking the live registry. Returns self for chaining."""
        if self._end is None:
            self._end = registry.snapshot(self.prefix)
            self.end_ts = time.time()
        return self

    @property
    def frozen(self):
        return self._end is not None

    def elapsed_s(self):
        return (self.end_ts or time.time()) - self.start_ts

    def base(self):
        """The base snapshot (plain data, already isolated)."""
        return self._base

    def delta(self):
        """``window_delta(base, end-or-live)`` — the full windowed
        view: scalar deltas + histogram slices."""
        end = self._end if self._end is not None \
            else registry.snapshot(self.prefix)
        return window_delta(self._base, end)

    def value(self, name, default=0):
        """Scalar delta of one counter/gauge (``default`` when the
        metric never appeared)."""
        v = self.delta().get(name, default)
        return v if isinstance(v, (int, float)) else default

    def hist(self, name):
        """Windowed histogram dict for ``name`` (None when absent or
        not a histogram)."""
        v = self.delta().get(name)
        return v if isinstance(v, dict) else None

    def percentile(self, name, q):
        """Windowed q-quantile of histogram ``name`` — exactly the
        observations that landed inside this window. None when the
        window saw none."""
        h = self.hist(name)
        if not h:
            return None
        return percentile_from_buckets(cumulative_buckets(h["buckets"]), q)


# -- XLA compile telemetry (jax.monitoring) --------------------------------

_monitoring_installed = False

# per-thread cumulative backend-compile seconds: XLA compiles run
# synchronously on the dispatching thread, so a delta of THIS value
# around a dispatch attributes exactly the compiles that dispatch
# triggered — unlike the process-global histogram sum, which would
# bill a concurrent engine's compile to whoever read the delta
# (profiler/accounting.py relies on this for per-request billing)
_thread_compile = threading.local()


def thread_compile_seconds():
    """Cumulative backend-compile seconds observed on the calling
    thread (0.0 where the jax.monitoring listener is unavailable)."""
    return getattr(_thread_compile, "seconds", 0.0)


def _install_jax_monitoring():
    """Fold jax's own compile events into the registry. Idempotent; the
    listener is module-global and permanent (jax has no unsubscribe), so
    it filters cheaply by prefix."""
    global _monitoring_installed
    if _monitoring_installed:
        return
    try:
        import jax.monitoring as jm

        c_count = counter("xla.compile.count")
        h_secs = histogram(
            "xla.compile.seconds",
            bounds=(0.01, 0.05, 0.1, 0.5, 1, 5, 10, 60, 300))
        c_trace = counter("xla.trace.count")

        def _on_duration(event, duration, **kw):
            # /jax/core/compile/backend_compile_duration is the real XLA
            # compile; jaxpr_trace_duration counts python traces
            if event.endswith("backend_compile_duration"):
                c_count.inc()
                h_secs.observe(duration)
                _thread_compile.seconds = getattr(
                    _thread_compile, "seconds", 0.0) + duration
            elif event.endswith("jaxpr_trace_duration"):
                c_trace.inc()

        jm.register_event_duration_secs_listener(_on_duration)
        _monitoring_installed = True
    except Exception:  # noqa: BLE001 — telemetry must never break dispatch
        _monitoring_installed = True


_install_jax_monitoring()
