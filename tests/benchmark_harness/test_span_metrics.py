"""The span reduction (``benchmarks/span_reduce.py``) and the per-layer
readers built on it, on a synthetic trace reckoned by hand
(``fixtures/spans.xplane.txt``), on counter maps, and on the trace a CPU
rehearsal writes. All on the CPU, in this process.

The synthetic slice is 20 us with device operations at 0-2, 4-6, 10-11,
14-15 and 19-20 us: 7 us busy and four idle gaps. The engine's thread
holds ``serving.step`` 1-16.5 (``serving.admit`` 1.5-12 with
``serving.prefill.forward`` 2.5-7 and ``serving.prefill.pool_write``
7-11.5, a runtime span inside it; ``serving.decode`` 12-16 with
``dispatch`` 12-13, ``readback`` 13-14.5, ``emit`` 14.5-16),
``serving.engine.no_work`` 17-18 and a second dispatch after the slice.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, span_reduce, trace_reduce  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
FIXTURE_MANIFEST = os.path.join(FIXTURES, "BENCHMARK.json")
US = 1e-6

_TRAIN_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[] fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "jit_train_step(42)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_convert_element_type(7)" } }
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 4000000 } } }
"""
_CPU_TRACE = 'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" ' \
    'events { metadata_id: 1 offset_ps: 0 duration_ps: 5 } } ' \
    'event_metadata { key: 1 value { id: 1 name: "serving.step" } } }'


def _planes(text):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text).planes


@pytest.fixture(scope="module")
def spans_text():
    with open(os.path.join(FIXTURES, "spans.xplane.txt")) as f:
        return f.read()


@pytest.fixture(scope="module")
def reduced(spans_text):
    return span_reduce.reduce_planes(_planes(spans_text))


class _Cell:
    def __init__(self, name, config="tiny-mistral"):
        self.name = name
        with open(os.path.join(FIXTURES, "configs", config + ".json")) as f:
            self.config = json.load(f)


def _ctx_on(tmp_path, monkeypatch, text, cell="synthetic", **extra):
    """A readers' ctx whose cell's trace is ``text``: written where the
    harness writes a cell's trace, under a TRACE_DIR of the test's own."""
    from jax.profiler import ProfileData

    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    where = tmp_path / cell / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return dict({"cell": _Cell(cell), "counters": {},
                 "trace": trace_reduce.reduce_planes(_planes(text)),
                 "peaks": harness.peaks_for("TPU v5 lite")}, **extra)


def _read(name, ctx):
    return harness.load_module(harness.reader_path(name)).read(
        dict(ctx, metric=name))


# -- the reduction --------------------------------------------------------

def test_the_slice_is_the_one_trace_reduce_takes(reduced, spans_text):
    plain = trace_reduce.reduce_planes(_planes(spans_text))
    assert reduced["window_s"] == pytest.approx(plain["window_s"]) \
        == pytest.approx(20 * US)
    assert reduced["busy_s"] == pytest.approx(plain["busy_s"]) \
        == pytest.approx(7 * US)


def test_a_gap_that_straddles_two_phases_is_split_by_overlap(reduced):
    # gap 2-4: admit's own time until 2.5, then the prefill's forward;
    # gap 11-14: pool write, admit, dispatch, read-back in turn. By its
    # middle the first would all be the forward's, the second dispatch's
    idle = reduced["idle_by_span"]
    assert idle["serving.admit"] == pytest.approx((0.5 + 0.5) * US)
    assert idle["serving.prefill.forward"] == pytest.approx((1.5 + 1) * US)
    assert idle["serving.decode.dispatch"] == pytest.approx(1 * US)
    assert idle["serving.decode.readback"] == pytest.approx(1 * US)
    assert sum(idle.values()) == pytest.approx(13 * US)


def test_a_gap_under_a_runtime_span_goes_to_the_program_phase(
        reduced, spans_text):
    # gap 6-10 lies under DeferredTpuAllocator::Allocate (8-9.5) inside
    # the pool write: trace_reduce names the runtime, this the program
    plain = dict(map(tuple, trace_reduce.reduce_planes(
        _planes(spans_text))["idle_gaps"]))
    assert plain["DeferredTpuAllocator::Allocate"] == pytest.approx(4 * US)
    idle = reduced["idle_by_span"]
    assert "DeferredTpuAllocator::Allocate" not in idle
    assert idle["serving.prefill.pool_write"] == pytest.approx(
        (3 + 0.5) * US)


def test_a_gap_under_no_program_phase_is_unowned(reduced):
    # 16.5-17 nothing; 18-19 only the client's own client.submit, which
    # is another thread's and not the program's
    idle = reduced["idle_by_span"]
    assert idle[span_reduce.UNOWNED] == pytest.approx(1.5 * US)
    assert "client.submit" not in idle and "serving.warmup" not in idle
    assert idle["serving.engine.no_work"] == pytest.approx(1 * US)
    assert idle["serving.step"] == pytest.approx(0.5 * US)


def test_owner_groups(reduced):
    assert reduced["idle_by_owner"] == pytest.approx(
        {"prefill": 6 * US, "decode": 2 * US, "host": 3.5 * US,
         "unowned": 1.5 * US})
    assert span_reduce.owner_group("serving.prefill.readback") == "prefill"
    assert span_reduce.owner_group("serving.decode.emit") == "host"
    assert span_reduce.owner_group("serving.engine.lock_wait") == "host"
    assert span_reduce.owner_group("train.step") == "host"


def test_busy_by_module_drops_the_fingerprint(reduced):
    assert span_reduce.module_name("jit_fn(123)") == "jit_fn"
    assert span_reduce.module_name("jit_f(x)") == "jit_f(x)"
    assert reduced["busy_by_module"] == pytest.approx(
        {"jit_llama_paged_decode": 7 * US,
         "jit_llama_paged_prefill": 1 * US, "jit_scatter": 1 * US})
    # the first starts with the slice and the last ends with it: cut
    assert "jit_llama_paged_decode" not in reduced["whole_modules"]
    assert reduced["whole_modules"]["jit_scatter"] == [
        1, pytest.approx(1 * US)]


def test_self_time_is_a_phase_less_its_children(reduced):
    self_s = reduced["phase_self_seconds"]
    assert self_s["serving.step"] == pytest.approx(1 * US)
    assert self_s["serving.admit"] == pytest.approx(1.5 * US)
    assert self_s["serving.prefill.pool_write"] == pytest.approx(4.5 * US)
    assert "serving.decode" not in self_s  # its children fill it
    # the dispatch after the slice's end is outside it
    assert self_s["serving.decode.dispatch"] == pytest.approx(1 * US)
    assert sum(self_s.values()) == pytest.approx(16.5 * US)


def test_context_tokens_come_from_the_dispatch_events_inside_the_slice(
        reduced):
    assert reduced["decode_context_tokens"] == 100  # not the 999 after it
    assert reduced["decode_dispatches"] == 1


def test_innermost_segments_and_gap_splitting_on_small_cases():
    seg = span_reduce.innermost_segments(
        [(0, 10, "a"), (2, 4, "b"), (3, 9, "c"), (12, 13, "d")])
    # c starts inside b and runs past it: cut to its parent
    assert seg == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 10, "a"),
                   (12, 13, "d")]
    assert span_reduce.split_gaps([(1, 3.5), (9, 12.5)], seg) == \
        pytest.approx({"a": 1 + 1, "b": 1, "c": 0.5, "d": 0.5,
                       "unowned": 2})
    assert span_reduce.split_gaps([(0, 1)], []) == {"unowned": 1}


def test_a_cpu_trace_and_a_program_without_phases(spans_text):
    assert span_reduce.reduce_planes(_planes(_CPU_TRACE)) is None
    # the parent's traces: device operations, no program phase
    bare = span_reduce.reduce_planes(_planes(_TRAIN_TRACE))
    assert bare["idle_by_span"] is None and bare["idle_by_owner"] is None
    assert bare["decode_dispatches"] == 0
    assert bare["phase_self_seconds"] == {}


# -- the readers ---------------------------------------------------------

def test_the_four_idle_shares_add_up_to_device_idle_share(
        tmp_path, monkeypatch, spans_text):
    ctx = _ctx_on(tmp_path, monkeypatch, spans_text)
    parts = {g: _read(f"idle_{g}_share.x", ctx) for g in
             ("in_prefill", "in_decode", "in_host", "unowned")}
    assert parts == pytest.approx({"in_prefill": 30.0, "in_decode": 10.0,
                                   "in_host": 17.5, "unowned": 7.5})
    assert sum(parts.values()) == pytest.approx(
        _read("device_idle_share.x", ctx)) == pytest.approx(65.0)


def test_prefill_busy_share_and_the_paged_roofline(
        tmp_path, monkeypatch, spans_text):
    ctx = _ctx_on(tmp_path, monkeypatch, spans_text)
    assert _read("prefill_busy_share.x", ctx) == pytest.approx(100 / 7)
    # 100 context tokens x (K, V) x 2 kv heads x 16 x 2 bytes in the
    # one 2 us kernel call, over 819 GB/s
    want = 100 * (100 * 2 * 2 * 16 * 2) / 2e-6 / 819e9
    assert _read("paged_attn_hbm_roofline.x", ctx) == pytest.approx(want)
    assert 0 < want < 100
    assert _read("step_device_ms.x", ctx) is None  # no train step in it


def test_step_device_ms_is_the_mean_train_step_module(
        tmp_path, monkeypatch):
    ctx = _ctx_on(tmp_path, monkeypatch, _TRAIN_TRACE, cell="train",
                  counters={})
    # the steps of 4 and 2 us; those the slice's edges cut (1 of ?, 4
    # of ?) are left out of the mean
    assert _read("step_device_ms.x", ctx) == pytest.approx(3e-3)
    # a trace from before the spans and the names: nothing to read
    for name in ("idle_in_prefill_share", "idle_in_decode_share",
                 "idle_in_host_share", "idle_unowned_share",
                 "prefill_busy_share", "paged_attn_hbm_roofline"):
        assert _read(name + ".x", ctx) is None, name


def test_the_step_split_adds_up_to_the_mean_step():
    h = lambda count, total: {"count": count, "sum": total}  # noqa: E731
    counters = {
        "serving.step_us": h(10, 600000.0), "serving.steps": 10,
        "serving.phase.prefill_forward_us": h(3, 60000.0),
        "serving.phase.prefill_pool_write_us": h(3, 150000.0),
        "serving.phase.prefill_readback_us": h(3, 30000.0),
        "serving.phase.decode_dispatch_us": h(10, 50000.0),
        "serving.phase.decode_readback_us": h(10, 250000.0)}
    ctx = {"counters": counters}
    got = {n: _read(n + ".x", ctx) for n in (
        "prefill_ms_per_step", "decode_ms_per_step", "host_ms_per_step",
        "pool_write_ms_per_prefill", "sched_step_mean_ms")}
    assert got == pytest.approx({
        "prefill_ms_per_step": 24.0, "decode_ms_per_step": 30.0,
        "host_ms_per_step": 6.0, "pool_write_ms_per_prefill": 50.0,
        "sched_step_mean_ms": 60.0})
    assert got["prefill_ms_per_step"] + got["decode_ms_per_step"] \
        + got["host_ms_per_step"] == pytest.approx(
            got["sched_step_mean_ms"])


@pytest.mark.parametrize("counters", [
    {}, {"serving.step_us": {"count": 5, "sum": 1.0}},
    {"serving.step_us": {"count": 0, "sum": 0.0},
     "serving.phase.prefill_pool_write_us": {"count": 0, "sum": 0.0}}],
    ids=["empty", "no-phase-histograms", "no-step-ran"])
def test_counter_readers_with_nothing_to_read(counters):
    ctx = {"counters": counters, "trace": None, "peaks": None}
    for name in ("prefill_ms_per_step", "decode_ms_per_step",
                 "host_ms_per_step", "pool_write_ms_per_prefill"):
        assert _read(name + ".x", ctx) is None, name
    # an untraced run or a CPU rehearsal: no trace, nothing from it
    for name in ("idle_in_prefill_share", "idle_unowned_share",
                 "prefill_busy_share", "step_device_ms",
                 "paged_attn_hbm_roofline"):
        assert _read(name + ".x", ctx) is None, name


def test_the_real_manifest_lists_the_new_metrics_where_they_read():
    with open(harness.MANIFEST) as f:
        manifest = json.load(f)
    assert harness.manifest_problems(manifest) == []
    by = {m["name"]: m for m in manifest["per_layer"]}
    sat, steady, train = ("mistral7b-batch-saturated",
                          "mistral7b-chat-steady", "gpt2m-train-seq1024")
    for family in ("prefill_ms_per_step", "decode_ms_per_step",
                   "host_ms_per_step", "idle_in_prefill_share",
                   "idle_in_decode_share", "idle_in_host_share",
                   "idle_unowned_share", "prefill_busy_share"):
        # one entry a family and moved metric, listing every cell it reads
        assert sat in by[family + ".serve"]["workloads"]
        assert by[family + ".serve"]["moves"] == "serve_tok_s"
        assert by[family + ".steady"]["workloads"] == [steady]
        assert by[family + ".steady"]["moves"] == "itl_p95_ms"
        assert by[family + ".serve"]["better"] == "lower"
    assert sat in by["pool_write_ms_per_prefill.serve"]["workloads"]
    assert by["step_device_ms.train"]["workloads"] == [train]
    assert by["paged_attn_hbm_roofline.serve"]["better"] == "higher"
    assert by["paged_attn_hbm_roofline.serve"]["unit"] == "%"
    sources = {"idle_": "program_span", "prefill_busy": "device_trace",
               "step_device": "device_trace",
               "paged_attn_hbm": "device_trace",
               "prefill_ms": "program_counter",
               "decode_ms": "program_counter",
               "host_ms": "program_counter",
               "pool_write_ms": "program_counter"}
    for name, m in by.items():
        for prefix, source in sources.items():
            if name.startswith(prefix):
                assert m["source"] == source, name


def test_the_operators_command_prints_the_tables(tmp_path, spans_text,
                                                 capsys):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(spans_text))
    assert span_reduce.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "idle 0.0000 s (65.00 %)" in out
    assert "serving.prefill.pool_write" in out
    assert "jit_llama_paged_decode" in out
    assert "context tokens 100 (100.0 a step)" in out
    cpu = tmp_path / "cpu.xplane.pb"
    cpu.write_bytes(ProfileData.text_proto_to_serialized_xspace(_CPU_TRACE))
    with pytest.raises(SystemExit, match="no device operation"):
        span_reduce.main([str(cpu)])


# -- the rehearsal's own trace ---------------------------------------------

def test_a_rehearsed_trace_holds_the_phases_nested_on_the_host_plane(
        tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    line, ctx, _notes = harness.run_cell(
        FIXTURE_MANIFEST, "tiny-serve-closed", 2**31 + 23, 1.5, True, True)
    assert line["correct"] and ctx["trace"] is None  # no device plane
    assert span_reduce.of_cell(ctx) is None
    path = trace_reduce.find_xplane(str(tmp_path / "tiny-serve-closed"))
    assert span_reduce.reduce_file(path) is None  # a CPU trace
    host = [line for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU" for line in plane.lines]
    engine = max(host, key=lambda ln: sum(
        ev.name == "serving.step" for ev in ln.events))
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats)) for ev in engine.events
              if ev.name.startswith("serving.")]
    steps = [(s, e) for n, s, e, _ in events if n == "serving.step"]
    dispatches = [(s, e, st) for n, s, e, st in events
                  if n == "serving.decode.dispatch"]
    assert len(steps) > 5 and len(dispatches) > 5
    # a step under way as the trace started or stopped has no event of
    # its own
    dispatches = [d for d in dispatches
                  if d[0] > steps[0][0] and d[1] < steps[-1][1]]
    assert all(any(a <= s and e <= b for a, b in steps)
               for s, e, _ in dispatches)
    assert all(st["context_tokens"] >= st["batch"] >= 1
               for _s, _e, st in dispatches)
    names = {n for n, *_ in events}
    assert {"serving.prefill.pool_write", "serving.prefill.forward",
            "serving.decode.readback", "serving.admit.plan",
            "serving.step_end"} <= names
