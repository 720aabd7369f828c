"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read. Kept with the benchmark so that every PR reduces a trace in
the same way.

What a v5e trace holds (looked at by hand, PERF.md section 5): one plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` has one event per
executed HLO instruction, named by its HLO text (``%fusion.12 = bf16[..]
fusion(..)``; a Pallas kernel is ``%<kernel name>.<n> = .. custom-call``),
with control-flow instructions (``%while``) enclosing the events of their
bodies; and a plane ``/host:CPU`` with a line for each host thread that
holds its ``jax.profiler.TraceAnnotation`` spans and the runtime's own
(``PJRT_LoadedExecutable_Execute``, ``H2D Dispatch``, ...), on the same
clock. (With jax's python tracer on there is an event for every Python
call as well; the benchmark leaves it off, see ``harness.TraceSlice``.)

- busy: the union of the ``XLA Ops`` intervals of a chip, averaged over
  the chips; window: first event start to last event end over all chips.
- per-op seconds: *self* time, an enclosing instruction's time less its
  enclosed events', summed by instruction name without its number and
  with its result type, so the parts add up to busy time.
- idle gaps: the intervals in which no chip-0 operation ran, each
  labelled by the innermost host event covering its middle
  (``unattributed`` where there is none: the host was in Python code that
  no span covers), summed by label.
"""

from __future__ import annotations

import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"
_TOP = 10


def find_xplane(trace_dir):
    """The one ``*.xplane.pb`` under ``trace_dir``, or None."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def op_key(hlo_text):
    """``%copy.2 = bf16[4097,16,8,128]{3,2,..} copy(..)`` ->
    (``copy``, ``copy bf16[4097,16,8,128]``): the instruction's name
    without its number, alone and with its result type."""
    m = re.match(r"%?([^\s=]+)\s*=\s*(\(?[a-z0-9]+\[[^\]]*\])?", hlo_text)
    if not m:
        return hlo_text[:80], hlo_text[:80]
    base = re.sub(r"\.\d+$", "", m.group(1))
    shape = (m.group(2) or "").lstrip("(")
    return base, (f"{base} {shape}" if shape else base)


def _intervals(line):
    return sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                   e.name) for e in line.events)


def _union(intervals):
    """Merged [start, end] list of sorted (start, end, ..) intervals."""
    out = []
    for iv in intervals:
        s, e = iv[0], iv[1]
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(intervals):
    """{event name: self ns} for sorted, properly nested intervals."""
    out = {}
    stack = []  # [end, name, self_ns]

    def close(until):
        while stack and stack[-1][0] <= until:
            _end, name, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + self_ns

    for s, e, name in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def _label_gaps(gaps, host_events):
    """[(label, ns)] for each gap: the innermost host event covering the
    gap's middle."""
    events = sorted(host_events)
    out = []
    active = []
    i = 0
    for s, e in gaps:
        mid = 0.5 * (s + e)
        while i < len(events) and events[i][0] <= mid:
            active.append(events[i])
            i += 1
        active = [ev for ev in active if ev[1] >= mid]
        label = min(active, key=lambda ev: ev[1] - ev[0])[2] \
            if active else "unattributed"
        out.append((label, e - s))
    return out


def reduce_planes(planes):
    """The reduction over ``ProfileData.planes``; None where no device
    operation was recorded (a CPU trace)."""
    planes = list(planes)  # ProfileData hands out an iterator
    per_chip = []
    for plane in planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == _OPS_LINE:
                iv = _intervals(line)
                if iv:
                    per_chip.append((plane.name, iv))
    if not per_chip:
        return None
    per_chip.sort()
    start = min(iv[0][0] for _, iv in per_chip)
    end = max(max(x[1] for x in iv) for _, iv in per_chip)
    unions = [_union(iv) for _, iv in per_chip]
    busy_ns = sum(sum(e - s for s, e in u) for u in unions) / len(unions)

    by_key, by_base, counts = {}, {}, {}
    for _, iv in per_chip:
        for _s, _e, name in iv:
            base = op_key(name)[0]
            counts[base] = counts.get(base, 0) + 1.0 / len(per_chip)
        for name, ns in _self_times(iv).items():
            base, key = op_key(name)
            by_key[key] = by_key.get(key, 0.0) + ns / len(per_chip)
            by_base[base] = by_base.get(base, 0.0) + ns / len(per_chip)

    host = []
    for plane in planes:
        if plane.name != _HOST_PLANE:
            continue
        for line in plane.lines:
            host.extend(_intervals(line))
    u0 = unions[0]
    gaps = [(a[1], b[0]) for a, b in zip(u0, u0[1:]) if b[0] > a[1]]
    by_label = {}
    for label, ns in _label_gaps(gaps, host):
        by_label[label] = by_label.get(label, 0.0) + ns

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:_TOP]]

    return {"chips": len(per_chip),
            "window_s": (end - start) / 1e9,
            "busy_s": busy_ns / 1e9,
            "op_seconds": {k: v / 1e9 for k, v in by_base.items()},
            "op_counts": counts,
            "device_ops": top(by_key),
            "idle_gaps": top(by_label)}


def reduce_file(path):
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)
