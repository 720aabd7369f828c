"""From the same profiler trace (``*.xplane.pb``) as ``trace_reduce`` to
what the *program's own* spans say about it: who owns each idle gap, how
busy time splits by program, what each phase costs, and how many context
tokens the decode steps read.

What a traced run of a served engine holds beyond what ``trace_reduce``
describes (looked at by hand, PERF.md section 5):

- on ``/host:CPU`` the engine's thread is one line among the host's (the
  line is named after the process, so it is found by what it holds: the
  line whose ``serving.*`` / ``train.*`` events cover the most time).
  Its events are the program's phase spans
  (``paddle_tpu.profiler.tracing.phase``: ``serving.step`` enclosing
  ``serving.admit``, ``serving.prefill.pool_write``,
  ``serving.decode.dispatch`` ...), properly nested, with the runtime's
  own events (``PjitFunction(..)``, ``DeferredTpuAllocator::Allocate``
  ...) inside them. The keyword arguments of a span are the event's
  ``stats``: ``serving.decode.dispatch`` has ``batch`` and
  ``context_tokens``;
- on ``/device:TPU:<n>`` the line ``XLA Modules`` has one event per
  executed program, named ``jit_<function>(<fingerprint>)``.

``reduce_planes`` returns, or None where no device operation was
recorded (a CPU rehearsal):

- ``idle_by_span``: chip 0's idle gaps (as ``trace_reduce`` finds them),
  each split **by overlap** among the innermost program phases of the
  engine's thread that cover it; the runtime's events are looked
  through; what no program phase covers is ``unowned``. Seconds by span
  name. ``idle_by_owner`` is the same in four groups: ``prefill``
  (``serving.prefill.*``), ``decode`` (``serving.decode.dispatch`` and
  ``.readback``), ``host`` (every other phase), ``unowned``. Both are
  None where the host plane has no program phase at all (a program from
  before the spans).
- ``busy_by_module``: seconds of the ``XLA Modules`` line by module name
  without its fingerprint. ``whole_modules``: ``[events, seconds]`` by
  name of those that lie strictly inside the slice: the profiler cuts
  the program that runs as the trace starts or stops to the slice's
  edge, and a mean over the cut ones would read short.
- ``phase_self_seconds``: a phase's time less its children's, by name.
- ``decode_context_tokens`` / ``decode_dispatches``: the sum of the
  ``context_tokens`` stat over the ``serving.decode.dispatch`` events
  wholly inside the slice, and their number.
- ``window_s``, ``busy_s``: the slice and chip 0's busy time, so that the
  shares are taken over what ``trace_reduce`` takes them over.

``python -m benchmarks.span_reduce <trace.xplane.pb>`` prints the tables.
"""

from __future__ import annotations

import os
import re
import sys
import warnings

from benchmarks import trace_reduce
from benchmarks.trace_reduce import (_DEVICE_PLANE, _HOST_PLANE, _OPS_LINE,
                                     _intervals, _union)

_MODULES_LINE = "XLA Modules"
# ``serving.phase.<phase>_us`` histograms of the model's calls in a step
PREFILL_PHASES = ("prefill_forward", "prefill_pool_write",
                  "prefill_readback")
DECODE_PHASES = ("decode_dispatch", "decode_readback")
_PROGRAM = ("serving.", "train.")
_DISPATCH = "serving.decode.dispatch"
UNOWNED = "unowned"


def owner_group(span_name):
    """The group of ``idle_by_owner`` a phase's gaps fall to."""
    if span_name == UNOWNED:
        return UNOWNED
    if span_name.startswith("serving.prefill."):
        return "prefill"
    if span_name in (_DISPATCH, "serving.decode.readback"):
        return "decode"
    return "host"


def module_name(event_name):
    """``jit_llama_paged_decode(1234567890)`` -> ``jit_llama_paged_decode``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def innermost_segments(spans):
    """Disjoint, sorted ``(start, end, name)``: for every moment some
    span of ``spans`` (one thread's, properly nested) covers, the
    innermost one. A child running past its parent is cut to it."""
    out = []
    stack = []   # [end, name], outermost first
    cursor = 0.0

    def close(until):
        nonlocal cursor
        while stack and stack[-1][0] <= until:
            end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for s, e, name in sorted(spans, key=lambda iv: (iv[0], -iv[1])):
        close(s)
        if stack:
            if s > cursor:
                out.append((cursor, s, stack[-1][1]))
            e = min(e, stack[-1][0])
        cursor = s  # spans come by start, so nothing is emitted past it
        if e > s:
            stack.append([e, name])
    close(float("inf"))
    return out


def split_gaps(gaps, segments):
    """{name: ns} of the sorted, disjoint ``gaps`` by their overlap with
    the sorted, disjoint ``segments``; what overlaps none is ``unowned``."""
    out = {}
    i = 0
    for s, e in gaps:
        while i < len(segments) and segments[i][1] <= s:
            i += 1
        owned = 0.0
        j = i
        while j < len(segments) and segments[j][0] < e:
            a, b, name = segments[j]
            part = min(b, e) - max(a, s)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                owned += part
            j += 1
        if e - s > owned:
            out[UNOWNED] = out.get(UNOWNED, 0.0) + (e - s) - owned
    return out


def _engine_line(host_lines):
    """Program spans ``(start, end, name, stats)`` of the host line on
    which they cover the most time: the engine's thread (or the train
    loop's). ``stats`` is read for the decode dispatches only."""
    best, best_ns = [], 0.0
    for line in host_lines:
        with warnings.catch_warnings():  # jaxlib's stats type lacks __module__
            warnings.simplefilter("ignore", DeprecationWarning)
            spans = [(float(ev.start_ns),
                      float(ev.start_ns + ev.duration_ns), ev.name,
                      dict(ev.stats) if ev.name == _DISPATCH else None)
                     for ev in line.events if ev.name.startswith(_PROGRAM)]
        covered = sum(e - s for s, e in _union(sorted(
            (s, e) for s, e, _n, _stats in spans)))
        if covered > best_ns:
            best, best_ns = spans, covered
    return best


def reduce_planes(planes):
    planes = list(planes)
    chips = []  # (plane name, its lines by name, its sorted operations)
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        if _DEVICE_PLANE.match(plane.name) and _OPS_LINE in lines:
            ops = _intervals(lines[_OPS_LINE])
            if ops:
                chips.append((plane.name, lines, ops))
    if not chips:
        return None
    # the slice, as trace_reduce takes it: first to last device operation
    start = min(ops[0][0] for _n, _l, ops in chips)
    end = max(iv[1] for _n, _l, ops in chips for iv in ops)
    _name, chip0, ops0 = min(chips, key=lambda c: c[0])
    busy = _union(ops0)
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]

    by_module, whole_modules = {}, {}
    for s, e, name in (_intervals(chip0[_MODULES_LINE])
                       if _MODULES_LINE in chip0 else []):
        key = module_name(name)
        by_module[key] = by_module.get(key, 0.0) + (e - s) / 1e9
        if s > start and e < end:
            whole = whole_modules.setdefault(key, [0, 0.0])
            whole[0] += 1
            whole[1] += (e - s) / 1e9

    spans = _engine_line(line for plane in planes
                         if plane.name == _HOST_PLANE for line in plane.lines)
    segments = innermost_segments((s, e, n) for s, e, n, _st in spans)
    idle_by_span = idle_by_owner = None
    if spans:
        idle_by_span = {k: v / 1e9
                        for k, v in split_gaps(gaps, segments).items()}
        idle_by_owner = {g: 0.0 for g in
                         ("prefill", "decode", "host", UNOWNED)}
        for name, sec in idle_by_span.items():
            idle_by_owner[owner_group(name)] += sec
    self_s = {}
    for s, e, name in segments:
        part = min(e, end) - max(s, start)
        if part > 0:
            self_s[name] = self_s.get(name, 0.0) + part / 1e9
    tokens = dispatches = 0
    for s, e, name, stats in spans:
        if name == _DISPATCH and s >= start and e <= end \
                and "context_tokens" in stats:
            tokens += int(stats["context_tokens"])
            dispatches += 1
    return {"window_s": (end - start) / 1e9,
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "idle_by_span": idle_by_span, "idle_by_owner": idle_by_owner,
            "busy_by_module": by_module, "whole_modules": whole_modules,
            "phase_self_seconds": self_s,
            "decode_context_tokens": tokens,
            "decode_dispatches": dispatches}


_BY_PATH = {}


def reduce_file(path):
    """``reduce_planes`` of the trace at ``path``, parsed once a process."""
    path = os.path.abspath(path)
    if path not in _BY_PATH:
        from jax.profiler import ProfileData

        _BY_PATH[path] = reduce_planes(ProfileData.from_file(path).planes)
    return _BY_PATH[path]


def of_cell(ctx):
    """The reduction of the trace the harness wrote for the cell a
    reader's ``ctx`` belongs to; None where there is none (an untraced
    run, a CPU rehearsal)."""
    from benchmarks import harness

    if ctx.get("trace") is None:
        return None
    path = trace_reduce.find_xplane(
        os.path.join(harness.TRACE_DIR, ctx["cell"].name))
    return reduce_file(path) if path else None


def idle_share(ctx, group):
    """Percent of the slice that chip 0 idled under the phases of
    ``group`` (``owner_group``); None where the trace has no phase."""
    spans = of_cell(ctx)
    if not spans or spans["idle_by_owner"] is None:
        return None
    return 100.0 * spans["idle_by_owner"][group] / spans["window_s"]


def phase_ms_per_step(ctx, *phases):
    """Milliseconds a scheduler step spent in ``phases`` over the window:
    the sums of their ``serving.phase.<phase>_us`` histograms over the
    count of ``serving.step_us`` (one observation a step, read with its
    sum, so the parts add up to ``sched_step_mean_ms``). None where the
    program has no such histogram or no step ran."""
    counters = ctx["counters"]
    step = counters.get("serving.step_us")
    hists = [counters.get(f"serving.phase.{p}_us") for p in phases]
    if not step or not step["count"] or any(h is None for h in hists):
        return None
    return sum(h["sum"] for h in hists) / step["count"] / 1e3


def _table(title, rows, total):
    print(title)
    for name, sec in sorted(rows.items(), key=lambda kv: -kv[1]):
        print(f"  {sec:10.4f} s  {100 * sec / total:6.2f} %  {name}")


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        sys.exit("usage: python -m benchmarks.span_reduce <trace.xplane.pb>")
    r = reduce_file(argv[0])
    if r is None:
        sys.exit("no device operation in this trace (a CPU run)")
    idle = r["window_s"] - r["busy_s"]
    print(f"slice {r['window_s']:.4f} s, busy {r['busy_s']:.4f} s, "
          f"idle {idle:.4f} s ({100 * idle / r['window_s']:.2f} %)")
    if r["idle_by_span"] is None:
        print("idle gaps: no program phase on the host plane")
    else:
        _table("idle gaps by owner (share of the slice)",
               r["idle_by_owner"], r["window_s"])
        _table("idle gaps by innermost program phase (share of the slice)",
               r["idle_by_span"], r["window_s"])
    _table("device busy by program (share of busy)", r["busy_by_module"],
           r["busy_s"])
    _table("host phases by self time (share of the slice)",
           r["phase_self_seconds"], r["window_s"])
    if r["decode_dispatches"]:
        print(f"decode steps {r['decode_dispatches']}, context tokens "
              f"{r['decode_context_tokens']} "
              f"({r['decode_context_tokens'] / r['decode_dispatches']:.1f}"
              " a step)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
