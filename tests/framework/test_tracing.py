"""Request-scoped tracing + telemetry export (docs/OBSERVABILITY.md).

Pins the tracing contract: span parent/child nesting (including across
threads and over the rpc wire), root-level sampling, ring-buffer
wraparound, the disabled no-op path, a served request producing a
complete submit→queue→prefill→decode→terminal trace, SLO-histogram
exemplars naming real trace_ids, and the OpenMetrics/Prometheus text
exposition round-tripping through an actual HTTP scrape.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as profiler
from paddle_tpu.core import resilience
from paddle_tpu.models import Llama, LlamaConfig
from paddle_tpu.profiler import export, metrics, tracing
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import spec as serving_spec


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


@pytest.fixture
def trace_flags():
    """Snapshot + restore the tracing flags around a test that mutates
    them (the registry is process-global across test files)."""
    names = ["FLAGS_trace_enable", "FLAGS_trace_sample",
             "FLAGS_trace_ring"]
    saved = paddle.get_flags(names)
    yield
    paddle.set_flags(saved)


def _names(recs):
    return [r["name"] for r in recs]


# -- span mechanics ------------------------------------------------------


def test_span_nesting_and_ambient_context():
    root = tracing.start_trace("t.root", rid=1)
    assert root.recording and root.trace_id and root.parent_id is None
    with tracing.span("t.child", parent=root) as child:
        assert tracing.current_trace_id() == root.trace_id
        with tracing.span("t.grand") as grand:  # ambient parent
            assert grand.parent_id == child.span_id
    assert tracing.current_trace_id() is None  # context restored
    root.end("DONE")
    tr = tracing.get_trace(root.trace_id)
    by = {r["name"]: r for r in tr}
    assert set(by) == {"t.root", "t.child", "t.grand"}
    assert by["t.child"]["parent"] == by["t.root"]["span"]
    assert by["t.grand"]["parent"] == by["t.child"]["span"]
    assert by["t.root"]["status"] == "DONE"
    assert all(r["trace"] == root.trace_id for r in tr)


def test_nesting_across_threads_via_explicit_parent():
    root = tracing.start_trace("x.root")
    seen = {}

    def worker():
        # a worker thread has no ambient context — the scheduler/driver
        # pattern is an explicit parent=, after which ambient nesting
        # works inside the thread
        assert tracing.current_trace_id() is None
        with tracing.span("x.thread", parent=root) as sp:
            seen["tid"] = tracing.current_trace_id()
            with tracing.span("x.inner") as inner:
                seen["inner_parent"] = inner.parent_id
            seen["span"] = sp.span_id

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    root.end()
    assert seen["tid"] == root.trace_id
    assert seen["inner_parent"] == seen["span"]
    by = {r["name"]: r for r in tracing.get_trace(root.trace_id)}
    assert by["x.thread"]["parent"] == by["x.root"]["span"]
    assert by["x.thread"]["tid"] != by["x.root"]["tid"]


def test_record_span_retroactive_and_attach_dict():
    root = tracing.start_trace("r.root")
    tracing.record_span("r.slice", root, 1234.5, step=7)
    ctx = root.context()
    assert ctx["trace_id"] == root.trace_id
    with tracing.attach(ctx):
        assert tracing.current_context() == ctx
        with tracing.span("r.adopted") as sp:
            assert sp.trace_id == root.trace_id
    root.end()
    by = {r["name"]: r for r in tracing.get_trace(root.trace_id)}
    assert by["r.slice"]["dur"] == pytest.approx(1234.5)
    assert by["r.slice"]["args"] == {"step": 7}
    assert by["r.adopted"]["parent"] == root.span_id


def test_disabled_is_single_global_noop(trace_flags):
    paddle.set_flags({"FLAGS_trace_enable": False})
    n_before = len(tracing.records())
    assert tracing.start_trace("off.root") is tracing.NULL
    assert tracing.span("off.child") is tracing.NULL
    with tracing.span("off.ctx"):
        assert tracing.current_trace_id() is None
    tracing.record_span("off.slice", tracing.NULL, 1.0)
    assert len(tracing.records()) == n_before
    # generous sanity bound on the disarmed path (the real budget is
    # pinned by tools/trace_gate.py): ~a flag read per call
    t0 = time.perf_counter()
    for _ in range(10_000):
        tracing.span("off.cost")
    per_call_us = (time.perf_counter() - t0) * 1e6 / 10_000
    assert per_call_us < 100


def test_sampling_zero_drops_roots_and_children(trace_flags):
    paddle.set_flags({"FLAGS_trace_sample": 0.0})
    # sample 0 disarms entirely (enabled iff rate > 0)
    assert tracing.start_trace("s.root") is tracing.NULL
    paddle.set_flags({"FLAGS_trace_sample": 1e-9})
    before = metrics.counter("trace.unsampled").value
    roots = [tracing.start_trace("s.root") for _ in range(50)]
    assert all(r is tracing.NULL for r in roots)  # P(hit) ~ 5e-8
    assert metrics.counter("trace.unsampled").value - before == 50
    # children of an unsampled root are the same null path
    assert tracing.span("s.child", parent=roots[0]) is tracing.NULL


def test_ring_wraparound(trace_flags):
    paddle.set_flags({"FLAGS_trace_ring": 8})
    try:
        for i in range(20):
            tracing.start_trace(f"w.{i}").end()
        recs = tracing.records()
        assert len(recs) == 8
        # oldest aged out, newest retained, order preserved
        assert _names(recs) == [f"w.{i}" for i in range(12, 20)]
    finally:
        paddle.set_flags({"FLAGS_trace_ring": 4096})  # resize clears


# -- the serving request path --------------------------------------------


def test_serving_request_yields_complete_trace(model):
    rng = np.random.default_rng(0)
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False)
    h = eng.submit(rng.integers(0, 255, (6,)).astype("int64"),
                   max_new_tokens=5)
    eng.run_until_idle()
    eng.close()
    assert h.status == "DONE" and h.trace_id is not None
    tr = tracing.get_trace(h.trace_id)
    names = _names(tr)
    assert "serving.request" in names
    assert "serving.queue_wait" in names
    assert "serving.prefill" in names
    # first token comes from prefill, the remaining 4 from decode steps
    assert names.count("serving.decode_step") == 4
    assert "serving.terminal" in names
    # every span parents inside the trace, root status is terminal
    ids = {r["span"] for r in tr}
    root = next(r for r in tr if r["name"] == "serving.request")
    assert root["parent"] is None and root["status"] == "DONE"
    assert root["args"]["tokens"] == 5
    for r in tr:
        assert r["parent"] is None or r["parent"] in ids
    # the whole trace exports as chrome/perfetto trace events
    ev = tracing.export_trace(h.trace_id)["traceEvents"]
    assert len(ev) == len(tr)
    assert all(e["ph"] == "X" and "trace_id" in e["args"] for e in ev)


def test_preempted_request_trace_records_preempt_and_reprefill(model):
    rng = np.random.default_rng(1)
    eng = ServingEngine(model, max_batch=2, block_size=4, max_seq_len=32,
                        num_blocks=8, temperature=0.0, background=False)
    h1 = eng.submit(rng.integers(0, 255, (8,)).astype("int64"),
                    max_new_tokens=12)
    h2 = eng.submit(rng.integers(0, 255, (8,)).astype("int64"),
                    max_new_tokens=12)
    eng.run_until_idle()
    eng.close()
    assert h1.status == h2.status == "DONE"
    preempted = [h for h in (h1, h2) if h.preempts > 0]
    assert preempted, "pool sized to force at least one preemption"
    tr = tracing.get_trace(preempted[0].trace_id)
    names = _names(tr)
    assert "serving.preempt" in names
    prefills = [r for r in tr if r["name"] == "serving.prefill"]
    assert any(p["args"]["reprefill"] for p in prefills)


def test_slo_exemplars_resolve_to_exportable_traces(model):
    # exemplars retain the per-bucket MAX ever observed while spans age
    # out of the bounded ring, so champions inherited from earlier test
    # files go stale and made this pin order-dependent (it failed on
    # the seed tree whenever test_serving ran first in the process).
    # Reset the two SLO histograms and drive fresh traffic: the
    # exemplar -> exportable-trace linkage is then deterministic.
    metrics.histogram("serving.ttft_us")._reset()
    metrics.histogram("serving.itl_us")._reset()
    rng = np.random.default_rng(5)
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False)
    h = eng.submit(rng.integers(0, 255, (6,)).astype("int64"),
                   max_new_tokens=5)
    eng.run_until_idle()
    eng.close()
    assert h.status == "DONE"
    snap = metrics.snapshot("serving.")
    for name in ("serving.ttft_us", "serving.itl_us"):
        exs = snap[name]["exemplars"]
        assert exs, f"{name} has no exemplars"
        for ex in exs.values():
            assert ex["trace_id"]
    # the max-TTFT exemplar names a trace the ring can still export
    worst = max((ex for ex in snap["serving.ttft_us"]
                 ["exemplars"].values()), key=lambda e: e["value"])
    assert worst["trace_id"] == h.trace_id
    assert tracing.get_trace(worst["trace_id"])
    # and the summary surfaces it as the Slow-requests view
    prof = profiler.Profiler()
    prof.start()
    prof.stop()
    table = prof.summary()
    assert "Slow requests" in table
    assert worst["trace_id"] in table


def test_degrade_events_carry_trace_id_and_summary_incidents():
    root = tracing.start_trace("d.root")
    with tracing.attach(root):
        resilience.degrade("test.traced", detail="incident smoke")
    root.end()
    from paddle_tpu.distributed import watchdog
    recs = [r for r in watchdog.flight_recorder().records()
            if r["tag"] == "degrade/test.traced"]
    assert recs and recs[-1]["trace"] == root.trace_id
    prof = profiler.Profiler()
    prof.start()
    prof.stop()
    table = prof.summary()
    assert "Recent incidents" in table
    assert "degrade/test.traced" in table


# -- rpc propagation -----------------------------------------------------


def _traced_double(x):
    with tracing.span("rpc.body"):
        return 2 * x


def test_rpc_context_propagates_over_the_wire():
    from paddle_tpu.distributed.rpc import WorkerInfo, _Agent
    a = _Agent("tr_a", 0, 2, store=None)
    b = _Agent("tr_b", 1, 2, store=None)
    try:
        for ag in (a, b):
            ag.workers = {
                "tr_a": WorkerInfo("tr_a", 0, "127.0.0.1", a.port),
                "tr_b": WorkerInfo("tr_b", 1, "127.0.0.1", b.port)}
        root = tracing.start_trace("rpc.root")
        with tracing.attach(root):
            assert a.call("tr_b", _traced_double, (21,), {}, 30) == 42
        root.end()
    finally:
        a.close()
        b.close()
    by = {r["name"]: r for r in tracing.get_trace(root.trace_id)}
    # client span, server span, and the remote fn's own span all share
    # one trace and nest: call -> serve -> body
    assert {"rpc.call", "rpc.serve", "rpc.body"} <= set(by)
    assert by["rpc.call"]["parent"] == root.span_id
    assert by["rpc.serve"]["parent"] == by["rpc.call"]["span"]
    assert by["rpc.body"]["parent"] == by["rpc.serve"]["span"]


# -- metrics export surface ----------------------------------------------


def test_percentile_estimation_from_buckets():
    h = metrics.Histogram("t.pct", bounds=(10, 100, 1000))
    for v in (5, 5, 50, 50, 50, 50, 500, 500, 500, 5000):
        h.observe(v)
    snap = h._snap()
    # p50 lands in the (10, 100] bucket, p99 in the overflow bucket
    assert 10 < snap["p50"] <= 100
    assert snap["p95"] > 100
    assert snap["p99"] <= 5000 and snap["p99"] > 500
    assert h.percentile(1.0) == 5000  # clamped to observed max
    assert metrics.Histogram("t.pct2").percentile(0.5) is None


def test_dump_json_has_timestamp_and_monotone_seq(tmp_path):
    metrics.counter("t.dump.seq").inc()
    p1, p2 = str(tmp_path / "m1.json"), str(tmp_path / "m2.json")
    before = time.time()
    metrics.dump(p1)
    metrics.dump(p2)
    d1, d2 = json.load(open(p1)), json.load(open(p2))
    assert d1["ts"] >= before - 1 and d2["ts"] >= d1["ts"]
    assert d2["seq"] == d1["seq"] + 1
    assert d1["metrics"]["t.dump.seq"] >= 1
    # the table shows estimated percentiles for histograms
    h = metrics.histogram("t.dump.hist")
    h.observe(3.0)
    assert "p99=" in metrics.dump(prefix="t.dump.")


def test_prometheus_text_roundtrips_through_http_scrape():
    c = metrics.counter("t.scrape.ctr")
    c.inc(3)
    metrics.gauge("t.scrape.g").set(2.5)
    h = metrics.histogram("t.scrape.h", bounds=(1, 10))
    root = tracing.start_trace("scrape.root")
    with tracing.attach(root):
        h.observe(7.0)
    root.end()
    h.observe(0.5)
    with export.MetricsServer() as srv:
        body = urllib.request.urlopen(
            srv.url("/metrics"), timeout=10).read().decode()
        assert body.rstrip().endswith("# EOF")
        parsed = export.parse_prometheus(body)
        assert parsed["t_scrape_ctr"]["type"] == "counter"
        assert parsed["t_scrape_ctr"]["value"] == c.value
        assert parsed["t_scrape_g"]["value"] == 2.5
        hist = parsed["t_scrape_h"]
        assert hist["count"] == 2 and hist["sum"] == 7.5
        # buckets are cumulative in the exposition
        assert hist["buckets"]["1"] == 1
        assert hist["buckets"]["10"] == 2
        assert hist["buckets"]["+Inf"] == 2
        assert hist["exemplars"]["10"]["trace_id"] == root.trace_id
        assert hist["exemplars"]["10"]["value"] == 7.0
        # healthz + trace endpoints
        hz = json.loads(urllib.request.urlopen(
            srv.url("/healthz"), timeout=10).read())
        assert hz["status"] == "ok" and "slo" in hz
        tj = json.loads(urllib.request.urlopen(
            srv.url(f"/traces/{root.trace_id}"), timeout=10).read())
        assert tj["traceEvents"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url("/traces/nope"), timeout=10)
        assert ei.value.code == 404


def test_engine_healthz_reports_dead_after_close(model):
    rng = np.random.default_rng(2)
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False)
    srv = eng.serve_metrics()
    assert eng.serve_metrics() is srv  # idempotent
    eng.submit(rng.integers(0, 255, (5,)).astype("int64"),
               max_new_tokens=2)
    eng.run_until_idle()
    hz = json.loads(urllib.request.urlopen(
        srv.url("/healthz"), timeout=10).read())
    assert hz["status"] == "ok" and hz["engine"]["closed"] is False
    eng.close()  # also closes the endpoint
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(srv.url("/healthz"), timeout=2)


def test_delta_rates_diff_successive_snapshots():
    d = export.DeltaRates(prefix="t.delta.")
    assert d.rates() == {}  # first call primes
    metrics.counter("t.delta.ctr").inc(10)
    rates = d.rates()
    assert rates["t.delta.ctr"] > 0


# -- phase spans: slices of the engine's thread -------------------------------


def _per_call_us(fn, n=5000, trials=5):
    """Median over trials of the mean time of one call, as
    tools/trace_gate.py measures the disarmed span."""
    import statistics
    outs = []
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        outs.append((time.perf_counter() - t0) * 1e6 / n)
    return statistics.median(outs)


def test_phase_costs_under_its_budget_with_no_profiler_session():
    def one_phase():
        with tracing.phase("serving.decode.dispatch", batch=4,
                           context_tokens=99):
            pass

    # a loaded runner can miss once; three misses are the code's
    reads = []
    for _ in range(3):
        reads.append(_per_call_us(one_phase))
        if reads[-1] < 5.0:
            break
    assert reads[-1] < 5.0, reads


def test_phase_feeds_its_histogram_and_step_has_none():
    assert tracing.phase_histogram_name("serving.decode.dispatch") \
        == "serving.phase.decode_dispatch_us"
    assert tracing.phase_histogram_name("serving.engine.no_work") \
        == "serving.phase.engine_no_work_us"
    name = tracing.phase_histogram_name("serving.sweep")
    before = metrics.snapshot("serving.")
    with tracing.phase("serving.step", step=1, running=0, queued=0):
        with tracing.phase("serving.sweep"):
            time.sleep(0.002)
    after = metrics.snapshot("serving.")
    assert after[name]["count"] - before[name]["count"] == 1
    assert 2000 <= after[name]["sum"] - before[name]["sum"] < 200000
    # serving.step feeds serving.step_us from the scheduler, as before
    assert "serving.phase.step_us" not in after
    assert after["serving.step_us"]["count"] \
        == before["serving.step_us"]["count"]
    # every catalogued phase has its histogram from import on
    for phase in tracing.PHASE_NAMES:
        if phase != "serving.step":
            assert tracing.phase_histogram_name(phase) in after


def test_span_and_record_span_stamp_ts_and_dur_alike():
    root = tracing.start_trace("stamp.root")
    t0 = time.time_ns() / 1000.0
    with tracing.span("stamp.live", parent=root):
        time.sleep(0.01)
    tracing.record_span("stamp.retro", root, 10000.0)
    t1 = time.time_ns() / 1000.0
    root.end()
    by = {r["name"]: r for r in tracing.get_trace(root.trace_id)}
    live, retro = by["stamp.live"], by["stamp.retro"]
    # both end "now": ts + dur is the wall clock at the end of the slice
    for r in (live, retro):
        assert t0 <= r["ts"] + r["dur"] <= t1
        assert isinstance(r["dur"], float)
    assert live["dur"] >= 10000.0 and retro["dur"] == 10000.0
    assert t0 - 1000 <= live["ts"] <= t0 + 5000


_SERVED_PROMPTS = (6, 9, 12, 7)
_SERVED_NEW = 5


@pytest.fixture(scope="module")
def served_under_profiler(model, tmp_path_factory):
    """A tiny background engine served inside a profiler session: the
    registry's delta over it, the handles, and the host plane's events
    of the written trace."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    rng = np.random.default_rng(5)
    logdir = str(tmp_path_factory.mktemp("phase_trace"))
    before = metrics.snapshot("serving.")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        eng = ServingEngine(model, max_batch=4, block_size=8,
                            max_seq_len=64, temperature=0.0)
        try:
            handles = []
            for wave in (_SERVED_PROMPTS[:2], _SERVED_PROMPTS[2:]):
                # the driver, idle, reaches its no_work wait; the next
                # submit ends that wait (a loaded runner needs the time)
                time.sleep(0.3)
                handles += [eng.submit(rng.integers(0, 255, (n,))
                                       .astype("int64"),
                                       max_new_tokens=_SERVED_NEW)
                            for n in wave]
                for h in handles:
                    h.result(timeout=120)
        finally:
            eng.close()
    finally:
        jax.profiler.stop_trace()
    after = metrics.snapshot("serving.")
    delta = {}
    for k, v in after.items():
        prev = before.get(k)
        if isinstance(v, dict):
            delta[k] = {"count": v["count"] - prev["count"],
                        "sum": v["sum"] - prev["sum"]}
        elif isinstance(v, (int, float)):
            delta[k] = v - (prev or 0)
    path, = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = []  # (line id, name, start, end, stats)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("serving."):
                    events.append((i, ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    return delta, handles, events


def test_served_engine_feeds_every_phase_histogram(served_under_profiler):
    delta, handles, _ = served_under_profiler
    assert all(h.status == "DONE" for h in handles)
    for phase in tracing.PHASE_NAMES:
        if phase == "serving.step":
            continue
        h = delta[tracing.phase_histogram_name(phase)]
        assert h["count"] > 0, phase
        assert h["sum"] > 0, phase
    assert delta["serving.phase.prefill_pool_write_us"]["count"] \
        == len(_SERVED_PROMPTS)
    assert delta["serving.phase.decode_dispatch_us"]["count"] \
        == delta["serving.phase.decode_readback_us"]["count"] \
        == delta["serving.phase.decode_emit_us"]["count"]


def test_children_of_a_step_account_for_it(served_under_profiler):
    delta, _, _ = served_under_profiler
    children = sum(delta[tracing.phase_histogram_name(p)]["sum"]
                   for p in ("serving.sweep", "serving.overload",
                             "serving.admit", "serving.decode",
                             "serving.step_end"))
    assert children >= 0.9 * delta["serving.step_us"]["sum"]
    assert delta["serving.step_us"]["count"] == delta["serving.steps"]
    # the split the benchmark reads: model calls and the rest
    model_us = sum(delta[f"serving.phase.{p}_us"]["sum"] for p in (
        "prefill_forward", "prefill_pool_write", "prefill_readback",
        "decode_dispatch", "decode_readback"))
    assert 0 < model_us < delta["serving.step_us"]["sum"]


def test_context_tokens_counter_is_the_context_each_step_read(
        served_under_profiler):
    delta, _, events = served_under_profiler
    # nobody is preempted: a request of p prompt tokens decodes its
    # tokens 2..n at contexts p+1 .. p+n-1
    want = sum(p + j + 1 for p in _SERVED_PROMPTS
               for j in range(_SERVED_NEW - 1))
    assert delta["serving.decode.context_tokens"] == want
    seen = [st for _l, name, _s, _e, st in events
            if name == "serving.decode.dispatch"]
    assert sum(st["context_tokens"] for st in seen) == want
    assert all(1 <= st["batch"] <= 4 for st in seen)


def test_request_spans_carry_the_step_that_ran_them(served_under_profiler):
    _, handles, events = served_under_profiler
    steps = {st["step"] for _l, name, _s, _e, st in events
             if name == "serving.step"}
    assert steps
    for h in handles:
        tr = tracing.get_trace(h.trace_id)
        mine = [r for r in tr if r["name"] in ("serving.prefill",
                                               "serving.decode_step")]
        assert len(mine) == _SERVED_NEW  # one prefill, n - 1 decodes
        assert all(r["args"]["step"] in steps for r in mine)
        got = [r["args"]["step"] for r in sorted(
            mine, key=lambda r: r["args"].get("token", -1))]
        assert got == sorted(got)  # the prefill first, then step by step
        assert all(a < b for a, b in zip(got[1:], got[2:]))


def test_phases_nest_on_the_engine_thread_in_the_profiler_trace(
        served_under_profiler):
    _, _, events = served_under_profiler
    steps = [(l, s, e) for l, name, s, e, _ in events
             if name == "serving.step"]
    line = steps[0][0]
    assert all(l == line for l, _s, _e in steps)  # one engine thread

    def inside_a_step(name):
        found = [(s, e) for l, n, s, e, _ in events
                 if n == name and l == line]
        assert found, name
        return all(any(a <= s and e <= b for _l, a, b in steps)
                   for s, e in found)

    for name in ("serving.decode.dispatch", "serving.decode.readback",
                 "serving.prefill.forward", "serving.prefill.pool_write",
                 "serving.admit.plan", "serving.step_end"):
        assert inside_a_step(name), name
    # the waits around a step are the engine thread's too, outside it
    for name in ("serving.engine.no_work", "serving.engine.lock_wait"):
        found = [(s, e) for l, n, s, e, _ in events
                 if n == name and l == line]
        assert found, name
        assert not any(a < s and e < b for _l, a, b in steps
                       for s, e in found)
    pool = [st for _l, n, _s, _e, st in events
            if n == "serving.prefill.pool_write"]
    assert all(st["layers"] == 2 and st["tokens"] % 8 == 0 for st in pool)


def _serve_with_lowered_text(monkeypatch, prompts, **engine_kw):
    """Serve ``prompts`` on a fresh tiny model; {AOT tag: head of the
    lowered text} of every serving program it built."""
    from paddle_tpu.models import llama as llama_mod

    seen = {}

    def wrap(jitted, tag):
        def call(*args):
            if tag not in seen:
                seen[tag] = jitted.lower(*args).as_text()[:300]
            return jitted(*args)
        return call

    monkeypatch.setattr(llama_mod, "_aot_wrap", wrap)
    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    eng = ServingEngine(m, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False, **engine_kw)
    try:
        for p in prompts:
            eng.submit(np.asarray(p, dtype="int64"), max_new_tokens=12)
            eng.run_until_idle()
    finally:
        eng.close()
    return seen


# prompt-lookup drafts need a repeated n-gram: the corpus the spec gate uses
_REPEATS = [list(p) for p in serving_spec.repetitive_prompts()]
_SHARED = list(range(10, 26))  # two blocks: the second prompt extends them


@pytest.mark.parametrize("engine_kw, prompts, want", [
    ({}, [_SHARED + [3, 4], _SHARED + [9, 9, 9]],
     {"llama.paged_prefill": "llama_paged_prefill",
      "llama.paged_extend": "llama_paged_extend",
      "llama.paged_decode": "llama_paged_decode"}),
    ({"kv_cache_dtype": "int8"}, [_SHARED + [3, 4], _SHARED + [9, 9, 9]],
     {"llama.paged_extend.q8": "llama_paged_extend_q8",
      "llama.paged_decode.q8": "llama_paged_decode_q8"}),
    ({"spec": True}, _REPEATS,
     {"llama.paged_spec": "llama_paged_spec"}),
    ({"spec": True, "kv_cache_dtype": "int8"}, _REPEATS,
     {"llama.paged_spec.q8": "llama_paged_spec_q8"}),
], ids=["plain", "int8", "spec", "spec-int8"])
def test_serving_programs_carry_stable_names(monkeypatch, engine_kw,
                                             prompts, want):
    seen = _serve_with_lowered_text(monkeypatch, prompts, **engine_kw)
    for tag, name in want.items():
        assert tag in seen, (tag, sorted(seen))
        assert f"module @jit_{name} " in seen[tag], seen[tag][:80]
    assert not any("@jit_fn" in text for text in seen.values())


def test_train_step_program_is_named_train_step():
    from paddle_tpu import nn, optimizer

    paddle.seed(0)
    m = nn.Linear(4, 4)
    opt = optimizer.SGD(learning_rate=0.05, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, opt, lambda mm, x: (mm(x) ** 2).mean())
    x = paddle.to_tensor(np.ones((2, 4), "float32"))
    assert "module @jit_train_step " in step.lower(x).as_text()[:200]
    first = float(np.asarray(step(x).numpy()))
    assert float(np.asarray(step(x).numpy())) < first
