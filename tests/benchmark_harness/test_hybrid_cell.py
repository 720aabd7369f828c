"""A rehearsal of the hybrid cell (``jamba3b-reasoning-saturated``) on the
CPU: the command end to end on a fixture manifest of its own
(``fixtures/hybrid/``: a tiny ``jamba`` configuration, mix and workload),
the new readers on a synthetic trace and counter maps reckoned by hand,
the operation counts, and the plain reference against itself. All in
this process; nothing here touches a TPU topology.

The synthetic slice is 24 us. Device operations: a fusion 0-1; a decode
step 2-9 (``ssm_update`` 2-4 and 4-6, ``paged_decode_chunked`` 6-7, a
fusion 7-9); a decode step 11-16 (``ssm_update`` 11-12.5 and 12.5-14, the
paged kernel 14-15, a fusion 15-16); a prefill 18-22 (``ssm_scan`` 18-19
and 19-20, ``flash_fwd`` 20-21, a fusion 21-22); an extend's fusion
23-24: 18 us busy.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, ops_count_ssm, trace_reduce  # noqa: E402
from benchmarks.reference import jamba_hybrid as reference  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "hybrid")
MANIFEST = os.path.join(FIXTURES, "BENCHMARK.json")
CELL = "tiny-hybrid"
REAL_CELL = "jamba3b-reasoning-saturated"
US = 1e-6
NEW_FAMILIES = ("ssm_update_busy_share", "ssm_update_hbm_roofline",
                "ssm_scan_busy_share", "ssm_scan_hbm_roofline",
                "hybrid_prefill_busy_share", "decode_step_mfu",
                "decode_step_hbm_roofline")

_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.1), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%ssm_update.3 = (f32[3,4,16,64]{3,2,1,0}, f32[4,64]{1,0}) custom-call(s32[4]{0} %act.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%paged_decode_chunked.4 = bf16[4,4,128]{2,1,0} custom-call(s32[4,8]{1,0} %tables.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 4 value { id: 4 name: "%ssm_scan.5 = (bf16[16,64]{1,0}, f32[16,64]{1,0}) custom-call(s32[1]{0} %len.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 5 value { id: 5 name: "%flash_fwd.6 = bf16[1,1,4,16,128]{4,3,2,1,0} custom-call(bf16[1,1,4,16,128]{4,3,2,1,0} %q.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 11 value { id: 11 name: "jit_jamba_paged_decode(1111111111)" } }
  event_metadata { key: 12 value { id: 12 name: "jit_jamba_paged_prefill(2222222222)" } }
  event_metadata { key: 13 value { id: 13 name: "jit_jamba_paged_extend(3333333333)" } }
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 12500000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 14000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 15000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 18000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 19000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 20000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 21000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 23000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 11 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 11 offset_ps: 2000000 duration_ps: 7000000 }
    events { metadata_id: 11 offset_ps: 11000000 duration_ps: 5000000 }
    events { metadata_id: 12 offset_ps: 18000000 duration_ps: 4000000 }
    events { metadata_id: 13 offset_ps: 23000000 duration_ps: 1000000 }
  }
}
planes { id: 2 name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "serving.step" } }
  event_metadata { key: 7 value { id: 7 name: "serving.decode.dispatch" } }
  event_metadata { key: 8 value { id: 8 name: "serving.decode.readback" } }
  stat_metadata { key: 2 value { id: 2 name: "batch" } }
  stat_metadata { key: 3 value { id: 3 name: "context_tokens" } }
  stat_metadata { key: 4 value { id: 4 name: "state_slots" } }
  lines { id: 7 name: "python3" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 9500000 }
    events { metadata_id: 7 offset_ps: 1500000 duration_ps: 1000000 stats { metadata_id: 2 int64_value: 3 } stats { metadata_id: 3 int64_value: 300 } stats { metadata_id: 4 int64_value: 3 } }
    events { metadata_id: 8 offset_ps: 2500000 duration_ps: 6500000 }
    events { metadata_id: 1 offset_ps: 10600000 duration_ps: 7000000 }
    events { metadata_id: 7 offset_ps: 10700000 duration_ps: 800000 stats { metadata_id: 2 int64_value: 3 } stats { metadata_id: 3 int64_value: 500 } stats { metadata_id: 4 int64_value: 3 } }
    events { metadata_id: 8 offset_ps: 11500000 duration_ps: 4500000 }
  }
}
"""


def _json(path):
    with open(path) as f:
        return json.load(f)


class _Cell:
    name = "synthetic-hybrid"
    config = _json(os.path.join(FIXTURES, "configs", "tiny-jamba.json"))
    traffic = _json(os.path.join(FIXTURES, "traffic", "tiny-reasoning.json"))


def _hist(count, total):
    return {"count": count, "sum": total}


# ten decode steps of 3 live slots over 400 context tokens each, and four
# prefills of 15 true tokens each
_COUNTERS = {
    "serving.phase.decode_dispatch_us": _hist(10, 9000.0),
    "serving.phase.prefill_forward_us": _hist(4, 8000.0),
    "serving.decode.context_tokens": 4000,
    "serving.ssm.state_slot_steps": 30, "serving.ssm.scan_tokens": 60}
# of the fixture's configuration: 3 state-space layers and 1 attention
# layer on a hidden of 32 (E 64, N 16, R 4, K 4; 4 heads of 8 on 1 KV head)
_MIXER = 32 * 128 + 64 * (4 + 32) + 4 * 64 + 64 * 32
_MATMUL = 3 * _MIXER + (32 * 32 * 2 + 32 * 8 * 2) + 4 * 3 * 32 * 64 \
    + 256 * 32
_UPDATE_BYTES = 3 * (2 * 64 * 16 * 4 + 3 * 64 * 4 + 2 * 16 * 4)


@pytest.fixture()
def ctx(tmp_path, monkeypatch):
    """A readers' ctx whose cell's trace is ``_TRACE``: written where the
    harness writes a cell's trace, under a TRACE_DIR of the test's own."""
    from jax.profiler import ProfileData

    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    where = tmp_path / _Cell.name / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_TRACE))
    planes = ProfileData.from_text_proto(_TRACE).planes
    return {"cell": _Cell, "counters": dict(_COUNTERS),
            "trace": trace_reduce.reduce_planes(planes),
            "peaks": harness.peaks_for("TPU v5 lite")}


def _read(name, ctx):
    return harness.load_module(harness.reader_path(name)).read(
        dict(ctx, metric=name))


# -- the manifests -------------------------------------------------------------

def test_the_fixture_manifest_mirrors_the_real_cell():
    fixture, real = _json(MANIFEST), _json(harness.MANIFEST)
    assert harness.manifest_problems(fixture) == []
    assert harness.manifest_problems(real) == []
    # one entry a family and moved metric, which lists every cell that
    # reads it: the fixture's entries are the real cell's, each listing
    # the fixture's cell alone
    mine = {m["name"]: dict(m, workloads=None) for m in real["per_layer"]
            if REAL_CELL in m.get("workloads", ())}
    assert all(m["moves"] == "serve_tok_s" for m in mine.values())
    assert all(m["workloads"] == [CELL] for m in fixture["per_layer"])
    assert {m["name"]: dict(m, workloads=None)
            for m in fixture["per_layer"]} == mine
    cell = harness.load_cell(harness.MANIFEST, REAL_CELL)
    assert cell.workload["driver"] == "serve_hybrid" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    cells = [w["name"] for w in real["workloads"]]
    for m in real["per_layer"]:  # no cell before it reads the new families
        if m["name"].split(".")[0] in NEW_FAMILIES:
            assert not set(m.get("workloads", cells)) & set(
                cells[:cells.index(REAL_CELL)])
    for name, layer, better in (
            ("ssm_update_hbm_roofline.serve", "kernels", "higher"),
            ("ssm_scan_hbm_roofline.serve", "kernels", "higher"),
            ("ssm_update_busy_share.serve", "kernels", "lower"),
            ("decode_step_mfu.serve", "model step", "higher"),
            ("decode_step_hbm_roofline.serve", "model step", "higher"),
            ("hybrid_prefill_busy_share.serve", "model step", "lower")):
        assert (mine[name]["layer"], mine[name]["better"],
                mine[name]["unit"]) == (layer, better, "%")
    # what the accepted benchmark had is as it was: entries were appended
    # behind the block-diffusion cell's (a later cell goes behind these in
    # turn, so nothing here pins the lists' ends)
    assert cells.index(REAL_CELL) == cells.index(
        "sdar30b-blockdiff-saturated") + 1
    configs = [c["name"] for c in real["configs"]]
    assert configs.index("ai21-jamba2-3b") == configs.index(
        "sdar-30b-a3b-chat") + 1
    serving = next(m for m in real["end_to_end"]
                   if m["name"] == "serve_tok_s")["workloads"]
    assert serving.index(REAL_CELL) == serving.index(
        "sdar30b-blockdiff-saturated") + 1


def test_the_real_cell_is_the_issues_traffic_and_configuration():
    cell = harness.load_cell(harness.MANIFEST, REAL_CELL)
    t, e = cell.traffic, cell.workload["engine"]
    assert (t["loop"], t["clients"], t["cycle"]) == ("closed", 256, 256)
    assert t["prompt_len"] == {"dist": "bounded_pareto", "alpha": 1.0,
                               "lo": 128, "hi": 1024}
    assert t["output_len"] == {"dist": "uniform", "lo": 1024, "hi": 3072}
    assert (t["lead_in_s"], t["drain_s"], t["order_seed"]) == (8, 30, 0)
    assert t["shared_prefix"]["share"] == 0.0
    # the issue's engine shape and nothing beside it: every flag at its
    # default, the prefill cap and the queue's bound among them
    assert e == {"slots": 128, "block_size": 16, "max_seq_len": 4096,
                 "bucket_cap": 1024}
    assert cell.workload["check_output"] == 512
    # overload control sheds a backlog past three quarters of the flag's
    # bound, and the loop opens with one of clients - slots
    from paddle_tpu.core import flags
    assert t["clients"] - e["slots"] \
        < 0.75 * flags.flag("FLAGS_serving_max_queue")
    assert e["bucket_cap"] == flags.flag("FLAGS_serving_prefill_bucket_cap")
    c = cell.config
    row = {"attn_layer_offset": 7, "attn_layer_period": 14,
           "expert_layer_offset": 1, "expert_layer_period": 2,
           "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 8192, "mamba_conv_bias": True,
           "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
           "mamba_expand": 2, "mamba_proj_bias": False,
           "max_position_embeddings": 262144, "model_type": "jamba",
           "num_attention_heads": 20, "num_experts": 1,
           "num_experts_per_tok": 1, "num_hidden_layers": 28,
           "num_key_value_heads": 1, "num_logits_to_keep": 1,
           "rms_norm_eps": 1e-06, "sliding_window": None,
           "tie_word_embeddings": True, "use_mamba_kernels": True,
           "vocab_size": 65536}
    assert {k: c[k] for k in row} == row   # the catalog row, key for key
    assert c["reduced"] == {} and c["torch_dtype"] == "bfloat16"
    assert {"initializer_range", "A_log", "D", "dt_bias",
            "ssm_state_dtype", "positional_encoding"} <= set(c["assumed"])
    assert "one v5e chip" in c["deployment"]
    # the mix's mean lengths are the issue's: 304 in, 2048 out
    from benchmarks import traffic
    mix = traffic.RequestMix(t, 1, c["vocab_size"])
    lens = np.array([mix.lengths(i) for i in range(256)])
    assert abs(lens[:, 0].mean() - 304) < 4 and lens[:, 0].max() <= 1024
    assert abs(lens[:, 1].mean() - 2048) < 4
    assert (lens.sum(axis=1) <= e["max_seq_len"]).all()


# -- the counts ------------------------------------------------------------------

def test_operation_counts_at_the_published_widths():
    fields = harness.load_cell(harness.MANIFEST, REAL_CELL).config
    s = ops_count_ssm.shapes(fields)
    assert (s["state_layers"], s["attn_layers"], s["channels"],
            s["head_dim"]) == (26, 2, 5120, 128)
    mixer = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    ffn = 3 * 2560 * 8192
    assert ops_count_ssm.matmul_params(fields) == 26 * mixer \
        + 2 * attention + 28 * ffn + 65536 * 2560
    # 6.06 GB of weights, tied head counted once
    assert 6.04e9 < ops_count_ssm.param_bytes(fields) < 6.07e9
    # a call: 128 slots' h read and written (2 x 41.9 MB) and 8 MB more
    one = ops_count_ssm.state_update_bytes(fields, 128)
    assert one == 128 * (2 * 5120 * 16 * 4 + 3 * 5120 * 4 + 2 * 16 * 4)
    assert 91e6 < one < 92e6
    assert ops_count_ssm.state_update_flops(fields, 128) \
        == 128 * 5120 * 16 * 7
    assert ops_count_ssm.scan_bytes_per_token(fields) == 5120 * 8 + 128
    step = ops_count_ssm.decode_step_bytes(fields, 128, 200_000)
    assert step == ops_count_ssm.param_bytes(fields) + 26 * one \
        + 200_000 * 2 * 128 * 2 * 2
    assert 8.4e9 < step < 8.7e9           # ~10.4 ms at 819 GB/s
    flops = ops_count_ssm.decode_step_flops(fields, 128, 200_000)
    assert flops == 2 * 128 * ops_count_ssm.matmul_params(fields) \
        + 26 * 128 * 5120 * 16 * 7 + 4 * 200_000 * 20 * 128 * 2
    assert 0.78e12 < flops < 0.80e12


# -- the readers on the synthetic trace ------------------------------------------

def test_the_kernels_shares_on_the_synthetic_trace(ctx):
    assert ctx["trace"]["busy_s"] == pytest.approx(18 * US)
    assert _read("ssm_update_busy_share.x", ctx) == pytest.approx(
        100 * 7 / 18)
    assert _read("ssm_scan_busy_share.x", ctx) == pytest.approx(
        100 * 2 / 18)
    # the prefill's 4 us and the extend's 1 us of 18
    assert _read("hybrid_prefill_busy_share.x", ctx) == pytest.approx(
        100 * 5 / 18)
    # 3 live slots a step; four calls of 7 us together
    assert ops_count_ssm.state_update_bytes(_Cell.config, 3) \
        == _UPDATE_BYTES
    want = 100 * _UPDATE_BYTES / 1.75e-6 / 819e9
    assert _read("ssm_update_hbm_roofline.x", ctx) == pytest.approx(want)
    assert 0 < want < 100
    # 15 true tokens a prefill x (E x 8 + 2 N x 4) bytes in a 1 us call
    want = 100 * 15 * (64 * 8 + 128) / 1e-6 / 819e9
    assert _read("ssm_scan_hbm_roofline.x", ctx) == pytest.approx(want)
    assert 0 < want < 100


def test_the_whole_steps_shares_on_the_synthetic_trace(ctx):
    # the steps of 7 and 5 us lie inside the slice; the one its edge cuts
    # is left out. 3 rows and 400 context tokens a step
    assert ops_count_ssm.matmul_params(_Cell.config) == _MATMUL == 61440
    flops = 2 * 3 * _MATMUL + 3 * (3 * 64 * 16 * 7) + 4 * 400 * 4 * 8
    want = 100 * flops / 6e-6 / 197e12
    assert _read("decode_step_mfu.x", ctx) == pytest.approx(want)
    assert 0 < want < 100
    small = 3 * (64 * 16 + 4 * 64 + 3 * 64)
    nbytes = (_MATMUL + small) * 2 + 3 * _UPDATE_BYTES + 400 * 2 * 8 * 2
    want = 100 * nbytes / 6e-6 / 819e9
    assert _read("decode_step_hbm_roofline.x", ctx) == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("family", NEW_FAMILIES)
def test_a_reader_finds_nothing_on_a_program_without_the_model(
        family, tmp_path, monkeypatch):
    """The parent commit, traced with this benchmark laid over it: a
    llama's trace and counters. Every new reader returns None and none
    raises; so does an untraced run."""
    from jax.profiler import ProfileData

    with open(os.path.join(os.path.dirname(FIXTURES),
                           "spans.xplane.txt")) as f:
        text = f.read()
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    where = tmp_path / _Cell.name / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    llama = {"cell": _Cell, "peaks": harness.peaks_for("TPU v5 lite"),
             "counters": {"serving.phase.decode_dispatch_us": _hist(5, 9.0),
                          "serving.phase.prefill_forward_us": _hist(2, 9.0),
                          "serving.decode.context_tokens": 100},
             "trace": trace_reduce.reduce_planes(
                 ProfileData.from_text_proto(text).planes)}
    assert _read(family + ".x", llama) is None
    assert _read(family + ".x", dict(llama, trace=None)) is None
    assert _read(family + ".x", {"cell": _Cell, "counters": {},
                                 "trace": None, "peaks": None}) is None


# -- the reference against itself ----------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models import Jamba, JambaConfig

    paddle.seed(0)
    model = Jamba(JambaConfig.tiny())
    model.eval()
    return reference.weights_of(model), {"num_heads": 4, "num_kv_heads": 1,
                                         "eps": 1e-6}


def test_the_reference_is_causal_and_carries_its_state(tiny):
    """Padding past the end changes nothing before it; the state after
    n tokens is where a forward over the rest continues from; a planted
    fault moves the state and what hangs on it."""
    weights, fields = tiny
    ids = np.random.default_rng(0).integers(3, 250, size=300)
    rows = np.arange(280, 300)
    logits, h, tail = reference.forward(weights, fields, ids, rows)
    assert logits.shape == (20, 256) and h.shape == (6, 64, 16) \
        and tail.shape == (6, 3, 64)
    # the sequence is padded to 512 inside: its own padding is not seen
    part, h_part, tail_part = reference.forward(weights, fields, ids[:256],
                                                np.arange(250, 256))
    again, _, _ = reference.forward(weights, fields, ids,
                                    np.arange(250, 256))
    np.testing.assert_allclose(part, again, atol=1e-5)
    assert reference.rel_rms(h_part, h) > 1e-3   # 44 tokens later
    # h rounded to bfloat16 a step, the padding run as tokens, another
    # request's state left in place: each moves h
    _, h_bf16, _ = reference.forward(weights, fields, ids, rows, h_bits=7)
    assert 1e-4 < reference.rel_rms(h_bf16, h) < 0.05
    # the state after the first 256 tokens, out of the same forward, and
    # what moved h at every position after them: delta, c, B
    *_, h_snap, moved = reference.forward(weights, fields, ids, rows,
                                          snap=256, fed=True)
    assert reference.rel_rms(h_snap, h_part) < 1e-6
    assert moved.shape == (6, 44, 2 * 64 + 16) and (moved[..., :64] > 0).all()
    np.testing.assert_allclose(
        reference.replay(weights, h_part, moved.transpose(1, 0, 2)), h,
        rtol=1e-4, atol=1e-6)
    _, h_stale, tail_stale = reference.forward(
        weights, fields, ids, rows, h0=(h_part, tail_part))
    assert reference.rel_rms(h_stale, h) > 0.05
    # the first layer's inputs hang on no state: its tail is as it was
    np.testing.assert_allclose(tail_stale[0], tail[0], atol=1e-6)
    padded = np.concatenate([ids[:150], np.zeros(106, np.int64), ids[150:]])
    _, h_pad, _ = reference.forward(weights, fields, padded, rows + 106)
    assert reference.rel_rms(h_pad, h) > 0.05
    assert reference.rel_rms(h, h) == 0.0
    # the 64th of a layer's elements that forget slowest keep a stale
    # state longest
    slow = reference.slowest(weights)
    assert slow.shape == h.shape and (slow.sum(axis=(1, 2)) == 16).all()
    assert reference.rel_rms(h_stale, h, slow) \
        > 2 * reference.rel_rms(h_stale, h)


def test_replay_is_the_forwards_recurrence_on_inputs_handed_in(tiny):
    """``replay`` from a state over the steps' own (delta, c, B) ends
    where the recurrence does; rounded to bfloat16 a step it does not."""
    weights, _ = tiny
    rng = np.random.default_rng(1)
    e, n, layers, steps = 64, 16, 6, 40
    fed = np.concatenate([
        np.log1p(np.exp(rng.standard_normal((steps, layers, e)) - 3)),
        rng.standard_normal((steps, layers, e + n)),
        np.ones((steps, layers, 1))], axis=-1).astype(np.float32)
    h0 = rng.standard_normal((layers, e, n)).astype(np.float32)
    want = h0.astype(np.float64)
    a = -np.arange(1, n + 1, dtype=np.float64)       # A[e, n] = -(n + 1)
    for t in range(steps):
        dt, c = fed[t, :, :e, None], fed[t, :, e:2 * e, None]
        b = fed[t, :, None, 2 * e:2 * e + n]
        want = np.exp(dt * a) * want + dt * c * b
    got = reference.replay(weights, h0, fed)
    assert reference.rel_rms(got, want) < 1e-5
    rounded = reference.replay(weights, h0, fed, h_bits=7)
    assert reference.LIMITS["replay_h"] < reference.rel_rms(rounded, want)


# -- the command end to end, in rehearsal ------------------------------------------------

def test_rehearsal_runs_the_hybrid_driver_end_to_end(capsys, tmp_path,
                                                     monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    rc = harness.main(["--rehearse", MANIFEST, "--workload", CELL,
                       "--seed", str(2**31 + 23), "--seconds", "1.5",
                       "--trace", "1"])
    assert rc == 0
    notes, last = (json.loads(ln) for ln in
                   capsys.readouterr().out.strip().splitlines()[-2:])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    # a rehearsal writes no number under a device metric's name
    assert last["metrics"] == {} and "breakdown" not in last
    cell = harness.load_cell(MANIFEST, CELL)
    # what needs no device trace is read from the counters
    assert {"decoded_per_step.serve", "sched_step_mean_ms.serve",
            "kv_used_share.serve", "kv_donated_share.serve",
            "decode_ahead_share.serve", "host_ms_per_step.serve"} \
        <= set(last["rehearsal"]["would_report"]) \
        <= {m["name"] for m in cell.per_layer}
    notes = notes["notes"]
    ref = notes["reference"]
    assert ref["ok"] and ref["tokens_each"] == 24
    # the check ran with the other slots live (4 slots: 2 check requests
    # beside 2 others)
    assert ref["load"]["live_slots_min"] >= 3
    assert len(ref["prompt_tokens"]) == 2
    assert ref["limits"] == dict(reference.LIMITS, margin=reference.MARGIN)
    assert all(ref["worst"][k] <= v for k, v in ref["limits"].items())
    assert set(ref["limits"]) == set(ref["worst"])
    assert ref["replayed_steps"] == 23 - ref["early_steps"][0] > 0
    # a rehearsal reads the planted faults as a traced run does: padding
    # that advances h and a slot's state left in place break the early
    # state's limit, h rounded to bfloat16 a step the replay's
    assert set(ref["planted"]) == {"bf16_state", "padding_advances",
                                   "stale_state", "other_layer"}
    for fault in ("padding_advances", "stale_state"):
        assert "start_h" in ref["planted"][fault]["breaks"]
    assert ref["planted"]["bf16_state"]["breaks"] == ["replay_h"]
    # the reports are what the full forward feeds its own recurrence at
    # those positions; those of the layer before are not
    assert ref["planted"]["other_layer"]["breaks"] == ["fed_inputs"]
    assert ref["worst"]["replay_h"] < 1e-5
    assert notes["step"]["steps"] > 0 and notes["step"]["step_ms"] > 0
    assert notes["state_bytes"] == 3 * 4 * (16 * 64 * 4 + 3 * 64 * 2)
    route = notes["kernel_route"]
    for kernel in ("ssm_scan", "ssm_update"):
        assert route[f"serving.kernel.{kernel}.pallas"] > 0
        assert route[f"serving.kernel.{kernel}.plain"] == 0
    assert route["serving.kernel.pallas"] > 0
    assert route["serving.kernel.dense"] == 0
    assert notes["ended"]["preempt"] == 0 and not notes["failures"]
