"""The harness: finds a cell's files by the names in ``BENCHMARK.json``,
runs its driver once, and prints the result line.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix. Their files are found by name, so a later PR adds a cell, a
configuration, a mix or a per-layer metric by adding files and manifest
entries and edits nothing here:

- ``<root>/configs/<config>.json``      the manifest's ``file``; ``<root>``
                                        is two directories above it
- ``<root>/traffic/<traffic>.json``     the mix, read by ``traffic.py``
- ``<root>/workloads/<name>.json``      driver, engine or step shape
- ``benchmarks/drivers/<driver>.py``    ``run(Run) -> dict``
- ``benchmarks/layer_metrics/<family>.py``  ``read(ctx) -> number | None``
  for the per-layer metric ``<family>[.<cell tag>]``
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import shutil
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
TRACE_DIR = os.path.join(REPO, ".bench_trace")

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    pass


def process_age_s():
    """Seconds since this process was started, from the kernel's record
    of its start (a hundredth of a second fine), so that set-up counts
    the interpreter's start and every import."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def _read_json(path, what):
    if not os.path.isfile(path):
        raise ManifestError(f"{what}: no file {os.path.relpath(path, REPO)}")
    with open(path) as f:
        return json.load(f)


def load_module(path):
    spec = importlib.util.spec_from_file_location(
        "benchmarks._loaded." + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def reader_path(metric_name):
    return os.path.join(BENCH, "layer_metrics",
                        metric_name.split(".")[0] + ".py")


def manifest_problems(manifest):
    """Inconsistencies of a manifest that the files cannot run with; an
    empty list where there are none."""
    bad = []
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    for entry in (manifest["configs"] + manifest["workloads"] + metrics):
        if not _NAME.match(entry["name"]):
            bad.append(f"name {entry['name']!r} has characters outside "
                       "letters, digits, _ . -")
    for m in metrics:
        if not _UNIT.match(m["unit"]):
            bad.append(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in _SOURCES:
            bad.append(f"{m['name']}: source {m['source']!r}")
    names = [e["name"] for e in metrics]
    bad += [f"metric {n} is named twice" for n in set(names)
            if names.count(n) > 1]
    configs = {c["name"] for c in manifest["configs"]}
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            bad.append(f"{w['name']}: no configuration {w['config']!r}")
        if "setup_s" not in e2e or not _reports(e2e["setup_s"], w["name"]):
            bad.append(f"{w['name']}: does not report setup_s")
    for m in metrics:
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: no cell {w!r}")
    for m in manifest["per_layer"]:
        moved = e2e.get(m["moves"])
        if moved is None:
            bad.append(f"{m['name']}: moves {m['moves']!r}, which is no "
                       "end-to-end metric")
            continue
        for w in cells:
            if _reports(m, w) and not _reports(moved, w):
                bad.append(f"{m['name']}: cell {w} reports it but not "
                           f"{m['moves']}")
        if not os.path.isfile(reader_path(m["name"])):
            bad.append(f"{m['name']}: no reader "
                       f"{os.path.relpath(reader_path(m['name']), REPO)}")
    return bad


class Cell:
    """One entry of ``workloads`` with everything its files say."""

    def __init__(self, manifest, name):
        entry = next((w for w in manifest["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise ManifestError(
                f"no workload {name!r}; the manifest has "
                f"{[w['name'] for w in manifest['workloads']]}")
        self.name = name
        self.chips = int(entry["chips"])
        conf = next(c for c in manifest["configs"]
                    if c["name"] == entry["config"])
        conf_path = os.path.join(REPO, conf["file"])
        root = os.path.dirname(os.path.dirname(conf_path))
        self.config = _read_json(conf_path, f"configuration {conf['name']}")
        self.traffic = _read_json(
            os.path.join(root, "traffic", entry["traffic"] + ".json"),
            f"traffic mix {entry['traffic']}")
        self.workload = _read_json(
            os.path.join(root, "workloads", name + ".json"),
            f"workload {name}")
        self.driver_path = os.path.join(
            BENCH, "drivers", self.workload["driver"] + ".py")
        if not os.path.isfile(self.driver_path):
            raise ManifestError(f"workload {name}: no driver "
                                f"{self.workload['driver']!r}")
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if _reports(m, name)]
        self.per_layer = [m for m in manifest["per_layer"]
                          if _reports(m, name)]


def load_cell(manifest_path, name):
    """The cell ``name`` of the manifest; every file it needs is looked
    for before anything is built."""
    manifest = _read_json(manifest_path, "manifest")
    bad = manifest_problems(manifest)
    if bad:
        raise ManifestError("; ".join(bad))
    return Cell(manifest, name)


def peaks_for(device_kind):
    table = _read_json(os.path.join(BENCH, "peaks.json"), "peaks")
    if device_kind not in table:
        raise ManifestError(
            f"no peaks known for device_kind {device_kind!r}: add it to "
            "benchmarks/peaks.json with its source")
    return table[device_kind]


def configure_jax():
    """Persistent compile cache at the program's fixed path (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), and every program persisted:
    jax's defaults skip those that compile in under a second, and a warm
    serving start recompiled 56 s of them (PERF.md, PR 21)."""
    import jax

    from paddle_tpu.utils import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def registry_delta(before, after):
    """after - before of two ``profiler.metrics.snapshot()`` maps:
    counters and gauges as numbers, histograms as their exact count and
    sum (their buckets are too coarse to read a tail from)."""
    out = {}
    for name, cur in after.items():
        prev = before.get(name)
        if isinstance(cur, dict):
            p = prev if isinstance(prev, dict) else {"count": 0, "sum": 0.0}
            out[name] = {"count": cur["count"] - p["count"],
                         "sum": cur["sum"] - p["sum"]}
        else:
            out[name] = cur - (prev if isinstance(prev, (int, float)) else 0)
    return out


def percentile(values, q):
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


class TraceSlice:
    """A profiler trace of a slice of the window, taken from a thread of
    its own so that the load goes on while the trace is written."""

    def __init__(self, cell_name):
        self.dir = os.path.join(TRACE_DIR, cell_name)
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread = None
        self.error = None

    @staticmethod
    def annotate(name):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def schedule(self, start_at, length_s):
        """Trace from ``start_at`` (``time.perf_counter``) for
        ``length_s`` seconds."""
        def run():
            import jax
            try:
                # jax's python tracer records every Python call: half a
                # million events in 3 s of serving, which slowed the
                # host-bound engine enough to queue requests that an
                # untraced run serves at once (PERF.md, PR 24). Off.
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                time.sleep(max(start_at - time.perf_counter(), 0.0))
                jax.profiler.start_trace(self.dir, profiler_options=options)
                time.sleep(length_s)
                jax.profiler.stop_trace()
            except BaseException as e:  # noqa: BLE001 — raised by finish()
                self.error = e

        self._thread = threading.Thread(target=run, name="bench-trace",
                                        daemon=True)
        self._thread.start()

    def finish(self):
        """The reduced trace (``trace_reduce.reduce_planes``), or None
        where no device operation was recorded."""
        from benchmarks import trace_reduce

        self._thread.join(timeout=300)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop")
        if self.error is not None:
            raise self.error
        path = trace_reduce.find_xplane(self.dir)
        if path is None:
            raise RuntimeError(f"the profiler wrote no trace to {self.dir}")
        return trace_reduce.reduce_file(path)


class Run:
    """What a driver is given."""

    def __init__(self, cell, seed, seconds, trace, rehearsal, devices):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearsal = bool(rehearsal)
        self.devices = devices

    def trace_slice(self):
        return TraceSlice(self.cell.name) if self.trace else None

    def memory_peak_bytes(self):
        """Peak bytes in use on the fullest chip so far (0 where the
        backend keeps no count, as the CPU does)."""
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)


def _devices(cell, rehearsal):
    import jax

    devs = jax.devices()
    if rehearsal:
        return devs[:cell.chips]
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        sys.exit(f"benchmark: cell {cell.name} needs {cell.chips} TPU "
                 f"chip(s); jax found {len(devs)} x {devs[0].platform}. "
                 "No number is made without the chip (see --rehearse).")
    return devs[:cell.chips]


def run_cell(manifest_path, workload, seed, seconds, trace, rehearsal):
    """Run one cell once; returns (result line as a dict, ctx the readers
    saw). Raises ManifestError before anything is built where a file or
    a name is missing."""
    cell = load_cell(manifest_path, workload)
    driver = load_module(cell.driver_path)
    readers = {m["name"]: load_module(reader_path(m["name"]))
               for m in cell.per_layer}
    devices = _devices(cell, rehearsal)
    if not rehearsal:  # a CPU compile is quick and its cache is noisy
        configure_jax()
    run = Run(cell, seed, seconds, trace, rehearsal, devices)
    out = driver.run(run)

    import jax

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    units = {m["name"]: m["unit"]
             for m in cell.end_to_end + cell.per_layer}
    ctx = dict(out["ctx"], cell=cell, rehearsal=rehearsal,
               memory_peak_bytes=out["memory_peak_bytes"],
               peaks=None if rehearsal else peaks_for(device["kind"]))
    values = {}
    if trace:
        reduced = ctx.get("trace")
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        elif not rehearsal:
            raise RuntimeError("traced run: no operation ran on the device")
        for name, reader in readers.items():
            value = reader.read(dict(ctx, metric=name))
            if value is not None:
                values[name] = float(value)
    else:
        for m in cell.end_to_end:
            values[m["name"]] = float(out["end_to_end"][m["name"]])
    line = {"correct": bool(out["correct"]),
            "attempted": int(out["attempted"]),
            "failed": int(out["failed"]),
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items()},
            "device": device}
    if trace and ctx.get("trace") is not None:
        line["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                             "idle_gaps": ctx["trace"]["idle_gaps"]}
    if rehearsal:
        # a CPU run writes no number under a device metric's name
        line["rehearsal"] = {"would_report": sorted(values)}
        line["metrics"] = {}
        line.pop("breakdown", None)
    return line, ctx, out.get("notes", {})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", metavar="MANIFEST", default=None,
                    help="run the cells of this other manifest (tiny "
                         "widths, tests/benchmark_harness/fixtures) on "
                         "whatever device there is, and print no metric")
    args = ap.parse_args(argv)
    try:
        line, _ctx, notes = run_cell(
            args.rehearse or MANIFEST, args.workload, args.seed,
            args.seconds, bool(args.trace), args.rehearse is not None)
    except ManifestError as e:
        sys.exit(f"benchmark: {e}")
    print(json.dumps({"notes": notes}), flush=True)
    print(json.dumps(line), flush=True)
    return 0
