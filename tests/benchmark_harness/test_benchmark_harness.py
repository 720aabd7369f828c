"""The benchmark's own tests: the manifest, the traffic generator, the
load generator's clock, the trace reduction, the plain references against
the system at tiny widths, and each driver end to end in rehearsal.
All on the CPU, in this process; nothing here touches a TPU topology.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import client as client_mod  # noqa: E402
from benchmarks import harness, ops_count, trace_reduce, traffic  # noqa: E402
from benchmarks.build import build_model  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
FIXTURE_MANIFEST = os.path.join(FIXTURES, "BENCHMARK.json")


def _manifest(path):
    with open(path) as f:
        return json.load(f)


# -- manifest ------------------------------------------------------------

@pytest.mark.parametrize("path", [harness.MANIFEST, FIXTURE_MANIFEST],
                         ids=["real", "fixture"])
def test_manifest_is_self_consistent(path):
    manifest = _manifest(path)
    assert harness.manifest_problems(manifest) == []
    for w in manifest["workloads"]:
        cell = harness.Cell(manifest, w["name"])  # every file is there
        assert os.path.isfile(cell.driver_path)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, f"{w['name']} reports no per-layer metric"
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(harness.load_module(
                harness.reader_path(m["name"])).read)


def test_real_manifest_keeps_the_contract_limits():
    manifest = _manifest(harness.MANIFEST)
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    for entry in manifest["configs"] + manifest["workloads"]:
        assert 1 <= len(entry["why"]) <= 200
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        fields = _manifest(os.path.join(REPO, c["file"]))
        assert set(c["reduced"]) == set(fields["reduced"])
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert all(w["chips"] == 1 for w in manifest["workloads"])


def test_a_missing_file_or_metric_is_an_error_before_anything_is_built():
    manifest = _manifest(FIXTURE_MANIFEST)
    manifest["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(harness.ManifestError, match="no-such-mix"):
        harness.Cell(manifest, manifest["workloads"][0]["name"])
    manifest = _manifest(FIXTURE_MANIFEST)
    manifest["per_layer"].append(dict(manifest["per_layer"][0],
                                      name="no_such_reader.x"))
    manifest["per_layer"][0]["moves"] = "train_tok_s"  # not in that cell
    bad = " ".join(harness.manifest_problems(manifest))
    assert "no reader" in bad and "reports it but not" in bad
    with pytest.raises(harness.ManifestError, match="no workload"):
        harness.Cell(_manifest(FIXTURE_MANIFEST), "never-heard-of-it")


# -- traffic ---------------------------------------------------------------

def _mix(seed, name="batch-saturated", seconds=50):
    params = _manifest(os.path.join(harness.BENCH, "traffic",
                                    name + ".json"))
    return traffic.RequestMix(params, seed, 32768, seconds=seconds)


def test_traffic_is_a_pure_function_of_the_seed():
    a, b, c = _mix(3000000001), _mix(3000000001), _mix(3000000002)
    pa, na = a.request(5)
    assert (pa == b.request(5)[0]).all() and na == b.request(5)[1]
    assert (pa != c.request(5)[0]).any()  # another seed, other tokens
    steady = [_mix(s, "chat-steady") for s in (7, 7)]
    due = [m.due_offsets(300) for m in steady]
    assert due[0] == due[1]
    assert all(y >= x for x, y in zip(due[0], due[0][1:]))


def test_every_seed_offers_the_same_schedule():
    """Sizes, order and arrivals belong to the mix; a queueing tail hangs
    on which requests meet, so the seed may not reshuffle them."""
    a, c = _mix(1), _mix(2**31 + 11)
    n = a.n
    assert [a.lengths(i) for i in range(2 * n)] \
        == [c.lengths(i) for i in range(2 * n)]
    assert [a.lengths(i) for i in range(n)] \
        == [a.lengths(i) for i in range(n, 2 * n)]  # round and round
    other = traffic.RequestMix(dict(a.p, order_seed=1), 1, 32768)
    assert [a.lengths(i) for i in range(n)] \
        != [other.lengths(i) for i in range(n)]
    assert sorted(a.lengths(i) for i in range(n)) \
        != sorted((1, 1) for _ in range(n))
    lo, hi = a.p["prompt_len"]["lo"], a.p["prompt_len"]["hi"]
    prompts = [a.lengths(i)[0] for i in range(n)]
    assert lo <= min(prompts) and max(prompts) <= hi
    # bounded Pareto, alpha 1 on 128-1536: mean ~347, median ~236
    assert 320 < np.mean(prompts) < 370 and 225 < np.median(prompts) < 250
    # an open loop's cycle is the window: rate x seconds requests whose
    # gaps sum to it, so any window of that length holds each one once
    seconds = _manifest(harness.MANIFEST)["run_seconds"]
    m, m2 = (_mix(s, "chat-steady", seconds) for s in (1, 9))
    assert m.n == round(m.p["arrivals"]["rate_rps"] * seconds)
    assert m.due_offsets(3 * m.n) == m2.due_offsets(3 * m.n)
    due = np.asarray(m.due_offsets(3 * m.n))
    assert due[m.n - 1] == pytest.approx(seconds, rel=1e-9)
    for start in (0.3, 5.0, 17.3):
        inside = np.flatnonzero((due >= start) & (due < start + seconds))
        assert len(inside) == m.n
        assert sorted(m.lengths(i) for i in inside) \
            == sorted(m.lengths(i) for i in range(m.n))


def test_shared_prefixes_and_bursts_are_data_only():
    params = dict(_mix(1).p, shared_prefix={"share": 0.5, "prefixes": 2,
                                            "len": 64})
    mix = traffic.RequestMix(params, 4, 32768, seconds=50)
    heads = [tuple(mix.request(i)[0][:64]) for i in range(mix.n)]
    shared = [h for h in set(heads) if heads.count(h) > 1]
    assert len(shared) == 2
    assert sum(heads.count(h) for h in shared) == mix.n // 2
    params = dict(_mix(1, "chat-steady").p,
                  arrivals={"process": "burst", "rate_rps": 8.0,
                            "burst_size": 4})
    bursty = traffic.RequestMix(params, 4, 32768, seconds=16)
    due = bursty.due_offsets(bursty.n)
    assert bursty.n == 128 and due[0] == due[3] < due[4] == due[7]
    assert due[-1] == pytest.approx(16.0, rel=1e-9)  # a whole cycle


# -- the load generator's clock ------------------------------------------------

class _FakeHandle:
    status = "RUNNING"

    def cancel(self):
        if self.status == "RUNNING":
            self.status = "CANCELLED"


class _FakeEngine:
    """Serves one request at a time, ``service_s`` each, in submit order;
    ``stall_at`` (index) makes that request take ``stall_s`` longer."""

    def __init__(self, service_s, stall_at=None, stall_s=0.0):
        self.service_s, self.stall_at, self.stall_s = \
            service_s, stall_at, stall_s
        self._q = []
        self._cv = threading.Condition()
        self._n = 0
        self._stop = False
        self._t = threading.Thread(target=self._serve, daemon=True)
        self._t.start()

    def submit(self, prompt, max_new_tokens, on_token):
        h = _FakeHandle()
        with self._cv:
            self._q.append((self._n, h, max_new_tokens, on_token))
            self._n += 1
            self._cv.notify()
        return h

    def _serve(self):
        while True:
            with self._cv:
                while not self._q and not self._stop:
                    self._cv.wait()
                if self._stop:
                    return
                i, h, n_new, on_token = self._q.pop(0)
            time.sleep(self.service_s
                       + (self.stall_s if i == self.stall_at else 0.0))
            if h.status == "CANCELLED":
                continue
            for _ in range(n_new):
                on_token(0)
            h.status = "DONE"

    def close(self):
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._t.join(timeout=10)
        assert not self._t.is_alive()


def _tiny_mix(loop, **extra):
    params = dict({"loop": loop, "cycle": 4,
                   "prompt_len": {"dist": "fixed", "value": 4},
                   "output_len": {"dist": "fixed", "value": 2}}, **extra)
    return traffic.RequestMix(params, 1, 100)


def test_open_loop_times_from_the_due_time():
    mix = _tiny_mix("open", arrivals={"process": "poisson",
                                      "rate_rps": 50.0})
    engine = _FakeEngine(0.002, stall_at=5, stall_s=0.4)
    try:
        load = client_mod.Client(engine, mix)
        load.start(horizon_s=0.6)
        time.sleep(0.7)
        load.stop()
        load.wait(10)
    finally:
        engine.close()
    recs = [r for r in load.records if r.complete]
    assert len(recs) >= 20
    # the generator kept its schedule through the stall ...
    late = [r.sent - r.due for r in recs]
    assert max(late) < 0.1
    # ... so the requests behind the stalled one waited, and their time to
    # first token, counted from when they were due, says so
    ttft = {r.index: r.times[0] - r.due for r in recs}
    assert ttft[4] < 0.1
    assert ttft[5] > 0.4 and ttft[6] > 0.3
    behind = [ttft[i] for i in range(6, 6 + 8)]
    assert all(x > y for x, y in zip(behind, behind[1:]))


def test_closed_loop_keeps_its_clients_in_flight_and_sees_early_ends():
    mix = _tiny_mix("closed", clients=3)
    engine = _FakeEngine(0.01)
    real_submit = engine.submit

    def submit(prompt, max_new_tokens, on_token):
        if engine._n == 4:  # this one the server ends without a token
            h = _FakeHandle()
            h.status = "TIMEOUT"
            engine._n += 1
            return h
        if engine._n == 6:
            engine._n += 1
            raise RuntimeError("queue full")
        return real_submit(prompt, max_new_tokens, on_token)

    engine.submit = submit
    try:
        load = client_mod.Client(engine, mix)
        load.start(horizon_s=0)
        time.sleep(0.5)
        load.stop()
        load.wait(10)
    finally:
        engine.close()
    recs = load.records
    assert len(recs) > 15
    assert all(r.ended for r in recs)
    # stopping withdrew what was in flight; those are not judged
    assert 1 <= sum(r.cancelled for r in recs) <= 3
    bad = [r for r in recs if not r.complete and not r.cancelled]
    assert len(bad) == 2 and sum(r.refused is not None for r in bad) == 1
    # never more than three outstanding: request i was sent only after
    # request i - 3 had ended
    for r in recs[3:]:
        earlier = [q for q in recs[:r.index] if q.complete]
        assert sum(q.times[-1] <= r.sent for q in earlier) >= r.index - 4


# -- trace reduction -----------------------------------------------------------

def test_trace_reduction_on_a_synthetic_trace():
    from jax.profiler import ProfileData

    with open(os.path.join(FIXTURES, "synthetic.xplane.txt")) as f:
        r = trace_reduce.reduce_planes(
            ProfileData.from_text_proto(f.read()).planes)
    us = 1e-6
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(15 * us)
    assert r["busy_s"] == pytest.approx(9 * us)  # union, not sum (11.5)
    # self time: the while encloses the fusion and the kernel
    assert r["op_seconds"] == pytest.approx(
        {"while": 2.5 * us, "fusion": 2 * us, "flash_fwd": 1.5 * us,
         "copy": 1 * us, "paged_decode_chunked": 2 * us})
    assert sum(r["op_seconds"].values()) == pytest.approx(r["busy_s"])
    assert r["op_counts"]["flash_fwd"] == 1
    assert r["device_ops"][0] == ["while u32[]", pytest.approx(2.5 * us)]
    assert ["copy bf16[4097,16,8,128]", pytest.approx(1 * us)] \
        in r["device_ops"]
    # the gap at 5-7 us lies under client.submit and, inside it, a jax
    # call: the innermost names it; another thread's span covers 8-10;
    # nothing covers 12-14
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        {"PjitFunction(scatter)": 2 * us, "client.submit": 2 * us,
         "unattributed": 2 * us})
    ctx = {"trace": r, "peaks": harness.peaks_for("TPU v5 lite")}
    read = lambda name: harness.load_module(  # noqa: E731
        harness.reader_path(name)).read(ctx)
    assert read("device_idle_share.x") == pytest.approx(100 * 6 / 15)
    assert read("paged_attn_busy_share.x") == pytest.approx(100 * 2 / 9)
    assert read("flash_roofline.x") is None  # no backward pass in it


def test_a_cpu_trace_has_no_device_to_reduce():
    from jax.profiler import ProfileData

    text = 'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" } }'
    assert trace_reduce.reduce_planes(
        ProfileData.from_text_proto(text).planes) is None


# -- readers and the yardstick's arithmetic -----------------------------------------

def test_counter_readers_and_unknown_devices():
    before = {"serving.steps": 10, "serving.decoded_tokens": 100,
              "xla.compile.count": 7,
              "serving.step_us": {"count": 10, "sum": 1e5, "buckets": {}},
              "serving.queue_wait_us": {"count": 2, "sum": 10.0}}
    after = {"serving.steps": 30, "serving.decoded_tokens": 700,
             "xla.compile.count": 7,
             "serving.step_us": {"count": 30, "sum": 9e5, "buckets": {}},
             "serving.queue_wait_us": {"count": 6, "sum": 8010.0},
             "serving.preempt": 1}
    delta = harness.registry_delta(before, after)
    assert delta["serving.preempt"] == 1
    ctx = {"counters": delta, "client": {"late_ms": list(range(101))},
           "kv_active_share": [0.1, 0.25, 0.2], "memory_peak_bytes": 9.5e9,
           "blocked_steps": 4, "blocked_s": 1.0}
    read = lambda name: harness.load_module(  # noqa: E731
        harness.reader_path(name)).read(ctx)
    assert read("decoded_per_step") == pytest.approx(30.0)
    assert read("sched_step_mean_ms") == pytest.approx(40.0)
    assert read("queue_wait_mean_ms") == pytest.approx(2.0)
    assert read("window_compiles") == 0
    assert read("gen_late_p95_ms") == pytest.approx(95.0)
    assert read("kv_used_share") == pytest.approx(25.0)
    assert read("hbm_peak_gb") == pytest.approx(9.5)
    assert read("step_ms") == pytest.approx(250.0)
    # a reader with nothing to read returns nothing
    empty = {"counters": {}, "client": {"late_ms": []}, "trace": None,
             "memory_peak_bytes": 0, "peaks": None}
    for m in _manifest(harness.MANIFEST)["per_layer"]:
        assert harness.load_module(harness.reader_path(m["name"])).read(
            dict(empty)) is None or m["name"].startswith("window_compiles")
    with pytest.raises(harness.ManifestError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")


def test_flops_and_roofline_arithmetic():
    # GPT-2 medium at 1024: 6 * 353.77M + 12 * 24 * 1024 * 1024
    per_token = ops_count.train_flops_per_token(353_772_544, 24, 1024, 1024)
    assert per_token == pytest.approx(2.4246e9, rel=1e-3)
    peak = harness.peaks_for("TPU v5 lite")
    assert ops_count.mfu_percent(40_000, per_token,
                                 peak["bf16_flops_per_s"]) \
        == pytest.approx(49.23, rel=1e-3)
    fwd, bwd = ops_count.flash_causal_flops(8, 16, 1024, 64)
    assert fwd == 2 * (2 * 8 * 16 * 1024 * 1024 * 64) / 2
    assert bwd == 2.5 * fwd
    share, bound = ops_count.roofline_percent(
        fwd + bwd, sum(ops_count.flash_bytes(8, 16, 1024, 64)),
        2 * (fwd + bwd) / peak["bf16_flops_per_s"], peak)
    assert share == pytest.approx(50.0) and bound == "compute"


# -- the plain references against the system, at tiny widths ------------------------

# initializer_range 0.15 (the real configurations have 0.02 at 64x the
# width): at hidden 64 a 0.02 draw leaves the attention scores near zero,
# every softmax uniform and the rotary base without effect
_TINY_LLAMA = {"vocab_size": 256, "hidden_size": 64,
               "intermediate_size": 128, "num_hidden_layers": 3,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "max_position_embeddings": 64, "rms_norm_eps": 1e-5,
               "rope_theta": 1000000.0, "tie_word_embeddings": False,
               "initializer_range": 0.15}


@pytest.fixture(scope="module")
def served_tiny_llama():
    import jax.numpy as jnp

    from benchmarks.drivers import serve
    from paddle_tpu.models import Llama
    from paddle_tpu.serving import ServingEngine

    model = build_model(Llama, serve._llama_config(_TINY_LLAMA),
                        "bfloat16", 2**31 + 5)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, 256, size=n) for n in (9, 30, 41)]
    with ServingEngine(model, temperature=0.0, dtype=jnp.bfloat16,
                       max_batch=4, block_size=4, max_seq_len=64,
                       bucket_cap=64, paged_kernel="pallas") as engine:
        handles = [engine.submit(p, max_new_tokens=12) for p in prompts]
        served = [[int(t) for t in h.result(timeout=300)] for h in handles]
    return model, prompts, served


def _worst_deficit(model, prompts, served, fields=None, drop_block=None):
    from benchmarks.reference import rope_gqa_swiglu as ref

    table, blocks, norm_w, head_w = ref.weights_of(model)
    if drop_block is not None:
        blocks = blocks[:drop_block] + blocks[drop_block + 1:]
    f = dict({"num_heads": 4, "num_kv_heads": 2, "rope_theta": 1000000.0,
              "rms_norm_eps": 1e-5}, **(fields or {}))
    worst = 0.0
    for prompt, toks in zip(prompts, served):
        ids = np.concatenate([prompt, toks, np.zeros(7, np.int64)])
        logits = ref.logits((table, blocks, norm_w, head_w), f, ids)
        worst = max(worst, ref.margin_check(logits, len(prompt), toks)[0])
    return worst


def test_served_tokens_lie_within_the_margin_of_the_plain_reference(
        served_tiny_llama):
    from benchmarks.reference import rope_gqa_swiglu as ref

    # prefill and decode through the paged cache and the (interpreted)
    # Pallas kernel, in bfloat16, against one float32 forward pass
    assert _worst_deficit(*served_tiny_llama) <= ref.MARGIN


@pytest.mark.parametrize("fault", [{"fields": {"rope_theta": 10000.0}},
                                   {"drop_block": 1}],
                         ids=["rope_theta", "skipped_layer"])
def test_a_wrong_model_fails_the_margin(served_tiny_llama, fault):
    from benchmarks.reference import rope_gqa_swiglu as ref

    assert _worst_deficit(*served_tiny_llama, **fault) > ref.MARGIN


def test_gpt_first_step_loss_equals_the_plain_reference():
    import paddle_tpu as paddle
    from benchmarks.drivers import train
    from benchmarks.reference import gpt2 as ref
    from paddle_tpu.models import GPT

    fields = _manifest(os.path.join(FIXTURES, "configs", "tiny-gpt2.json"))
    config = train._gpt_config(fields)
    assert config.vocab_size == 256
    model = build_model(GPT, config, "bfloat16", 7)
    batch = traffic.train_batches(
        {"batch": 2, "seq_len": 32, "distinct_batches": 1}, 7,
        fields["vocab_size"])[0]
    assert batch.max() < fields["vocab_size"]
    got = float(model.loss(paddle.to_tensor(batch),
                           paddle.to_tensor(batch)).numpy())
    f = {"num_heads": config.num_heads,
         "layer_norm_epsilon": config.layer_norm_epsilon}
    want = ref.loss(ref.weights_of(model), f, batch)
    assert abs(got - want) / want <= ref.LOSS_TOLERANCE
    # and the reference is not insensitive: other weights, another loss
    other = ref.loss(ref.weights_of(build_model(GPT, config, "bfloat16", 8)),
                     f, batch)
    assert abs(other - want) / want > 1e-4


def test_weights_are_a_pure_function_of_a_large_seed():
    from paddle_tpu.models import GPT, GPTConfig

    def table(seed):
        m = build_model(GPT, GPTConfig.tiny(), "bfloat16", seed)
        assert all(str(p.dtype).endswith("bfloat16") for p in m.parameters())
        return np.asarray(m.wte.weight._data.astype("float32"))

    a, b, c = table(2**31 + 9), table(2**31 + 9), table(2**31 + 10)
    assert (a == b).all() and (a != c).any()
    assert 0.015 < a.std() < 0.025
    import paddle_tpu as paddle
    # building inside jit left no tracer behind as the process's RNG state
    assert np.isfinite(paddle.rand([2]).numpy()).all()


# -- each driver end to end, in rehearsal ----------------------------------------------

@pytest.mark.parametrize("workload,trace", [
    ("tiny-serve-closed", 0), ("tiny-serve-open", 0), ("tiny-train", 0),
    ("tiny-serve-closed", 1), ("tiny-train", 1)])
def test_rehearsal_runs_the_driver_end_to_end(workload, trace, capsys):
    rc = harness.main(["--rehearse", FIXTURE_MANIFEST, "--workload",
                       workload, "--seed", str(2**31 + 17), "--seconds",
                       "1.5", "--trace", str(trace)])
    assert rc == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] >= 1
    # a rehearsal writes no number under a device metric's name
    assert last["metrics"] == {} and "breakdown" not in last
    cell = harness.load_cell(FIXTURE_MANIFEST, workload)
    want = cell.per_layer if trace else cell.end_to_end
    assert set(last["rehearsal"]["would_report"]) <= \
        {m["name"] for m in want}
    if not trace:
        assert set(last["rehearsal"]["would_report"]) == \
            {m["name"] for m in want}


def test_a_fixture_cell_the_harness_has_never_seen(tmp_path, capsys):
    """Adding a cell is adding data files and manifest entries."""
    manifest = _manifest(FIXTURE_MANIFEST)
    root = tmp_path / "newcells"
    for sub in ("configs", "traffic", "workloads"):
        (root / sub).mkdir(parents=True)
    for src, dst in (("configs/tiny-mistral.json", "configs/other.json"),
                     ("workloads/tiny-serve-open.json",
                      "workloads/other-bursty.json")):
        (root / dst).write_text(
            open(os.path.join(FIXTURES, src)).read())
    mix = _manifest(os.path.join(FIXTURES, "traffic", "tiny-open.json"))
    mix["arrivals"] = {"process": "burst", "rate_rps": 6.0, "burst_size": 3}
    (root / "traffic" / "bursty.json").write_text(json.dumps(mix))
    manifest["configs"].append(dict(manifest["configs"][0], name="other",
                                    file=str(root / "configs/other.json")))
    manifest["workloads"].append(
        {"name": "other-bursty", "config": "other", "traffic": "bursty",
         "chips": 1, "why": "new"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "tiny-serve-open" in m.get("workloads", []):
            m["workloads"].append("other-bursty")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    line, ctx, _notes = harness.run_cell(str(path), "other-bursty", 3, 1.5,
                                         False, True)
    assert line["correct"] and line["attempted"] > 0
    assert set(line["rehearsal"]["would_report"]) == \
        {"itl_p95_ms", "setup_s"}


def test_no_chip_no_number(capsys):
    """Outside a rehearsal the run stops, with another code than 0 and no
    result line, before anything is built."""
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", "gpt2m-train-seq1024", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "TPU" in str(e.value.code)
    assert capsys.readouterr().out == ""
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", "no-such-cell", "--seed", "1",
                      "--seconds", "1"])
    assert e.value.code not in (0, None)
