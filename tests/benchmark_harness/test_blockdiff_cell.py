"""A rehearsal of the block-diffusion cell (``sdar30b-blockdiff-saturated``)
on the CPU: the command end to end on a fixture manifest of its own
(``fixtures/blockdiff/``: a tiny ``sdar_moe`` configuration, mix and
workload), the new readers on a synthetic trace and counter maps reckoned
by hand, the operation counts, and the plain reference against itself.
All in this process; nothing here touches a TPU topology.

The synthetic slice is 24 us. Device operations: a fusion 0-1; a block
step 2-9 (``moe_gmm_swiglu_step`` 2-5, ``moe_gmm_step`` 5-6,
``paged_block_chunked`` 6-8, a fusion 8-9); a block step 11-16 (3 + 1 +
1 us of the same kernels); a prefill 18-21 (``moe_gmm_swiglu`` 18-20,
``moe_gmm`` 20-21); an extend's fusion 23-24: 17 us busy. The engine's
thread holds two ``serving.decode.dispatch`` spans inside the slice, of
300 and 500 context tokens.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, ops_count_moe, trace_reduce  # noqa: E402
from benchmarks.reference import sdar_moe_blockdiff as reference  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "blockdiff")
MANIFEST = os.path.join(FIXTURES, "BENCHMARK.json")
CELL = "tiny-blockdiff"
REAL_CELL = "sdar30b-blockdiff-saturated"
US = 1e-6

_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.1), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%moe_gmm_swiglu_step.3 = bf16[64,48]{1,0} custom-call(s32[4]{0} %te.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%moe_gmm_step.4 = bf16[64,64]{1,0} custom-call(s32[4]{0} %te.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 4 value { id: 4 name: "%paged_block_chunked.5 = bf16[4,32,32]{2,1,0} custom-call(s32[4,32]{1,0} %tables.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 5 value { id: 5 name: "%moe_gmm_swiglu.7 = bf16[64,48]{1,0} custom-call(s32[4]{0} %te.2), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 6 value { id: 6 name: "%moe_gmm.8 = bf16[64,64]{1,0} custom-call(s32[4]{0} %te.2), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 11 value { id: 11 name: "jit_sdar_block_step(1111111111)" } }
  event_metadata { key: 12 value { id: 12 name: "jit_sdar_paged_prefill(2222222222)" } }
  event_metadata { key: 13 value { id: 13 name: "jit_sdar_paged_extend(3333333333)" } }
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 14000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 15000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 18000000 duration_ps: 2000000 }
    events { metadata_id: 6 offset_ps: 20000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 23000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000000
    events { metadata_id: 11 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 11 offset_ps: 2000000 duration_ps: 7000000 }
    events { metadata_id: 11 offset_ps: 11000000 duration_ps: 5000000 }
    events { metadata_id: 12 offset_ps: 18000000 duration_ps: 3000000 }
    events { metadata_id: 13 offset_ps: 23000000 duration_ps: 1000000 }
  }
}
planes { id: 2 name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "serving.step" } }
  event_metadata { key: 7 value { id: 7 name: "serving.decode.dispatch" } }
  event_metadata { key: 8 value { id: 8 name: "serving.decode.readback" } }
  event_metadata { key: 9 value { id: 9 name: "serving.decode.emit" } }
  event_metadata { key: 10 value { id: 10 name: "serving.block.unmask" } }
  event_metadata { key: 11 value { id: 11 name: "serving.block.commit" } }
  stat_metadata { key: 2 value { id: 2 name: "batch" } }
  stat_metadata { key: 3 value { id: 3 name: "context_tokens" } }
  stat_metadata { key: 4 value { id: 4 name: "rows" } }
  stat_metadata { key: 5 value { id: 5 name: "denoise_slots" } }
  stat_metadata { key: 6 value { id: 6 name: "commit_slots" } }
  lines { id: 7 name: "python3" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 9500000 }
    events { metadata_id: 7 offset_ps: 1500000 duration_ps: 1000000 stats { metadata_id: 2 int64_value: 4 } stats { metadata_id: 3 int64_value: 300 } stats { metadata_id: 4 int64_value: 16 } stats { metadata_id: 5 int64_value: 3 } stats { metadata_id: 6 int64_value: 1 } }
    events { metadata_id: 8 offset_ps: 2500000 duration_ps: 6500000 }
    events { metadata_id: 9 offset_ps: 9000000 duration_ps: 1500000 }
    events { metadata_id: 10 offset_ps: 9200000 duration_ps: 300000 }
    events { metadata_id: 11 offset_ps: 9600000 duration_ps: 800000 }
    events { metadata_id: 1 offset_ps: 10600000 duration_ps: 7000000 }
    events { metadata_id: 7 offset_ps: 10700000 duration_ps: 800000 stats { metadata_id: 2 int64_value: 4 } stats { metadata_id: 3 int64_value: 500 } stats { metadata_id: 4 int64_value: 16 } stats { metadata_id: 5 int64_value: 2 } stats { metadata_id: 6 int64_value: 2 } }
    events { metadata_id: 8 offset_ps: 11500000 duration_ps: 4500000 }
  }
}
"""


def _json(path):
    with open(path) as f:
        return json.load(f)


class _Cell:
    name = "synthetic-blockdiff"
    config = _json(os.path.join(FIXTURES, "configs", "tiny-sdar.json"))
    traffic = _json(os.path.join(FIXTURES, "traffic", "tiny-blockdiff.json"))


def _hist(count, total):
    return {"count": count, "sum": total}


# ten block steps of 4 slots x 4 positions x 2 experts a token in 2 layers
_COUNTERS = {
    "serving.phase.decode_dispatch_us": _hist(10, 9000.0),
    "serving.decode.context_tokens": 4000,
    "serving.moe.rows": 10 * 2 * 32, "serving.moe.experts_hit": 10 * 2 * 6,
    "serving.moe.max_rows": 10 * 2 * 9,
    "serving.blockdiff.denoise_forwards": 26,
    "serving.blockdiff.commit_forwards": 14,
    "serving.blockdiff.blocks_committed": 14,
    "serving.blockdiff.tokens_unmasked": 52}


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
    mine = {m["name"]: m for m in real["per_layer"]
            if m.get("workloads") == [REAL_CELL]}
    assert len(mine) == 23 and all(n.endswith(".sdar") for n in mine)
    assert all(m["moves"] == "serve_tok_s" for m in mine.values())
    assert {m["name"]: dict(m, workloads=[REAL_CELL])
            for m in fixture["per_layer"]} == mine
    cell = harness.load_cell(harness.MANIFEST, REAL_CELL)
    assert cell.workload["driver"] == "serve_blockdiff"
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    for m in real["per_layer"]:  # no other cell reads the new families
        if m["name"].split(".")[0] in (
                "forwards_per_block", "moe_rows_per_expert",
                "moe_gmm_busy_share", "moe_gmm_hbm_roofline",
                "block_attn_hbm_roofline", "block_attn_busy_share",
                "blockdiff_prefill_busy_share", "step_mfu"):
            assert m["workloads"] == [REAL_CELL]
    for name, layer, better, unit in (
            ("moe_gmm_hbm_roofline.sdar", "kernels", "higher", "%"),
            ("block_attn_hbm_roofline.sdar", "kernels", "higher", "%"),
            ("step_mfu.sdar", "model step", "higher", "%"),
            ("forwards_per_block.sdar", "scheduler", "lower", "count"),
            ("moe_rows_per_expert.sdar", "expert layer", "higher", "count")):
        assert (mine[name]["layer"], mine[name]["better"],
                mine[name]["unit"]) == (layer, better, unit)


def test_the_real_cell_is_the_issues_traffic_and_engine_shape():
    cell = harness.load_cell(harness.MANIFEST, REAL_CELL)
    t, e = cell.traffic, cell.workload["engine"]
    assert (t["loop"], t["clients"], t["cycle"]) == ("closed", 128, 128)
    assert t["prompt_len"] == {"dist": "bounded_pareto", "alpha": 1.0,
                               "lo": 128, "hi": 1536}
    assert t["output_len"] == {"dist": "uniform", "lo": 256, "hi": 512}
    assert (t["block_length"], t["denoise_steps"], t["remasking"]) == \
        (4, 2, "low_confidence_static")
    assert (t["lead_in_s"], t["drain_s"]) == (6, 30)
    assert t["shared_prefix"]["share"] == 0.0
    assert e == {"slots": 64, "block_size": 16, "max_seq_len": 2048,
                 "bucket_cap": 2048}
    c = cell.config
    want = {"hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
            "num_key_value_heads": 4, "num_experts": 128,
            "num_experts_per_tok": 8, "moe_intermediate_size": 768,
            "vocab_size": 151936, "num_hidden_layers": 6,
            "norm_topk_prob": True, "rope_theta": 1000000,
            "max_position_embeddings": 32768, "torch_dtype": "bfloat16",
            "tie_word_embeddings": False, "decoder_sparse_step": 1}
    assert {k: c[k] for k in want} == want
    assert list(c["reduced"]) == ["num_hidden_layers"]
    assert {"block_length", "schedule", "mask_token_id", "qk_norm",
            "rotary_pairing", "weights"} <= set(c["assumed"])
    assert "stage 1 of 8" in c["deployment"]


# -- the counts ------------------------------------------------------------------

def test_operation_counts_at_the_published_widths():
    fields = dict(harness.load_cell(harness.MANIFEST, REAL_CELL).config,
                  block_length=4)
    layer, head = ops_count_moe.params_a_row_multiplies(fields)
    # q and o 2048x4096 each, k and v 2048x512 each, the router over 128,
    # 8 experts of 3 x 2048 x 768
    assert layer == 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128 \
        + 8 * 3 * 2048 * 768 == 56_885_248
    assert head == 2048 * 151936
    # every expert hit by 2048 rows: 128 x 9.4 MB of weights and the rows
    # in and out of both calls
    nbytes = ops_count_moe.expert_layer_bytes(128, 2048, fields, 2)
    assert nbytes == 128 * 3 * 2048 * 768 * 2 + 2048 * 2 * (2048 + 768) * 2
    assert 1.20e9 < nbytes < 1.24e9
    assert ops_count_moe.attention_bytes(35000, fields, 2) \
        == 35000 * 2 * 4 * 128 * 2
    flops = ops_count_moe.block_step_flops(256, 35000, fields)
    assert flops == 2.0 * 256 * (6 * layer + head) \
        + 4.0 * 32 * 128 * 4 * 35000 * 6
    assert 0.33e12 < flops < 0.36e12


# -- the readers on the synthetic trace ------------------------------------------

def test_the_kernels_shares_on_the_synthetic_trace(ctx):
    assert ctx["trace"]["busy_s"] == pytest.approx(17 * US)
    # both products of the block steps and of the prefill: 3+1+3+1+2+1
    assert _read("moe_gmm_busy_share.x", ctx) == pytest.approx(
        100 * 11 / 17)
    # the block attention of the two steps: 2 + 1
    assert _read("block_attn_busy_share.x", ctx) == pytest.approx(
        100 * 3 / 17)
    # the prefill's 3 us and the extend's 1 us of 17
    assert _read("blockdiff_prefill_busy_share.x", ctx) == pytest.approx(
        100 * 4 / 17)
    # 400 context tokens a step x (K, V) x 2 kv heads x 32 x 2 bytes in a
    # mean call of 1.5 us
    want = 100 * (400 * 2 * 2 * 32 * 2) / 1.5e-6 / 819e9
    assert _read("block_attn_hbm_roofline.x", ctx) == pytest.approx(want)
    assert 0 < want < 100
    # a layer of a step: 6 experts hit x 3 x 64 x 48 x 2 bytes and 32
    # rows x 2 x (64 + 48) x 2 bytes, in a pair of calls of 3 + 1 us
    nbytes = 6 * 3 * 64 * 48 * 2 + 32 * 2 * (64 + 48) * 2
    want = 100 * nbytes / 4e-6 / 819e9
    assert _read("moe_gmm_hbm_roofline.x", ctx) == pytest.approx(want)
    assert 0 < want < 100


def test_step_mfu_is_the_useful_flops_over_the_whole_block_steps(ctx):
    # the steps of 7 and 5 us lie inside the slice; the one its edge cuts
    # is left out. 16 rows and 400 context tokens a step
    fields = dict(_Cell.config, block_length=4)
    layer = 2 * 64 * 128 + 2 * 64 * 64 + 64 * 8 + 2 * 3 * 64 * 48
    assert ops_count_moe.params_a_row_multiplies(fields) \
        == (layer, 64 * 256)
    flops = 2.0 * 16 * (2 * layer + 64 * 256) + 4.0 * 4 * 32 * 4 * 400 * 2
    want = 100 * flops / 6e-6 / 197e12
    assert _read("step_mfu.x", ctx) == pytest.approx(want)
    assert 0 < want < 100


def test_the_counter_readers(ctx):
    assert _read("forwards_per_block.x", ctx) == pytest.approx(40 / 14)
    assert _read("moe_rows_per_expert.x", ctx) == pytest.approx(32 / 6)


@pytest.mark.parametrize("family", [
    "forwards_per_block", "moe_rows_per_expert", "moe_gmm_busy_share",
    "moe_gmm_hbm_roofline", "block_attn_hbm_roofline",
    "block_attn_busy_share", "blockdiff_prefill_busy_share", "step_mfu"])
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
    from paddle_tpu.models import SDAR, SDARConfig

    paddle.seed(0)
    model = SDAR(SDARConfig.tiny())
    model.eval()
    cfg = model.config
    fields = reference.fields_of({
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "norm_topk_prob": cfg.norm_topk_prob,
        "block_length": cfg.block_length})
    return model, reference.weights_of(model), fields


def test_the_reference_on_some_rows_is_the_reference_on_all(tiny):
    """The head runs on the asked rows only, which changes no logit, and
    padding past a block changes nothing before it."""
    _model, weights, fields = tiny
    ids = np.random.default_rng(0).integers(3, 250, size=23)
    whole = np.asarray(reference.logits(weights, fields, ids))
    assert whole.shape == (23, 256)
    rows = np.asarray([20, 21, 22])
    np.testing.assert_allclose(
        reference.logits(weights, fields, ids, rows=rows), whole[rows],
        atol=1e-6)
    padded = np.concatenate([ids[:20], np.full(12, 7)])
    np.testing.assert_allclose(
        np.asarray(reference.logits(weights, fields, padded))[:20],
        whole[:20], atol=1e-6)
    # block-causal, not causal: a position sees the rest of its block
    changed = ids.copy()
    changed[22] = (changed[22] + 1) % 250
    other = np.asarray(reference.logits(weights, fields, changed))
    assert np.abs(other[20] - whole[20]).max() > 1e-5   # same block
    np.testing.assert_allclose(other[:20], whole[:20], atol=1e-6)


def test_the_schedule_and_the_rule():
    assert reference.unmask_counts(4, 2) == [2, 2]
    assert reference.unmask_counts(3, 2) == [2, 1]
    assert reference.unmask_counts(1, 2) == [1]
    assert reference.unmask_counts(4, 4) == [1, 1, 1, 1]
    assert reference.unmask_counts(4, 3) == [2, 1, 1]
    masked = np.asarray([False, True, True, True])
    pick = reference.pick_unmasked([0.9, 0.2, 0.5, 0.5], masked, 2)
    assert pick.tolist() == [False, False, True, True]
    pick = reference.pick_unmasked([0.9, 0.5, 0.5, 0.5], masked, 1)
    assert pick.tolist() == [False, True, False, False]  # the earlier one


def test_generate_replays_the_procedure(tiny):
    _model, weights, fields = tiny
    prompt = np.random.default_rng(1).integers(3, 250, size=10)
    seen = []
    out = reference.generate(weights, fields, prompt, 11, denoise_steps=2,
                             mask_token_id=255, pad_to=32,
                             on_forward=seen.append)
    assert len(out) == 11
    assert out == reference.generate(weights, fields, prompt, 11,
                                     denoise_steps=2, mask_token_id=255)
    # 10 = 2 whole blocks + 2 given: the first block has 2 masked
    # positions (two forwards of one each, and the commit), then two
    # blocks of 2 + 1 forwards, then one more for the eleventh token
    assert [f["seq_len"] for f in seen] == [8] * 3 + [12] * 3 + [16] * 3 \
        + [20] * 3
    assert [f["commit"] for f in seen] == [False, False, True] * 4
    assert seen[0]["ids"][:2] == [int(t) for t in prompt[8:]]
    assert seen[0]["masked"].tolist() == [False, False, True, True]
    assert seen[3]["masked"].all() and not seen[5]["masked"].any()
    assert seen[2]["ids"][2:] + seen[5]["ids"] + seen[8]["ids"] \
        + seen[11]["ids"][:1] == out


def test_a_planted_fault_moves_the_comparison(tiny):
    """``compare_forward`` reads nothing on the reference's own forward
    and something on a forward with an expert dropped or with the
    experts stored in a type below the configuration's; at these toy
    widths the dropped expert breaks ``logit_rms`` alone (the chip runs
    at the published widths: PERF.md, PR 28)."""
    _model, weights, fields = tiny
    ids = np.random.default_rng(2).integers(3, 250, size=16)
    rows = np.arange(12, 16)
    lg = np.asarray(reference.logits(weights, fields, ids, rows=rows))
    top = lg.max(-1)
    record = {"tokens": lg.argmax(-1), "logits": top,
              "probs": 1.0 / np.exp(lg - top[:, None]).sum(-1)}

    def read(**fault):
        errs = reference.compare_forward(record, reference.logits(
            weights, fields, ids, rows=rows, **fault))
        assert all(errs[k].shape == (4,) for k in reference.KINDS)
        return reference.readings(errs)

    clean = read()
    assert set(clean) == {"logit", "logit_rms", "margin", "margin_rms",
                          "prob", "prob_rms"}
    assert clean["logit"] < 1e-6 and clean["margin"] == 0.0 \
        and clean["prob"] < 1e-6
    limits = reference.LIMITS
    assert set(limits) <= set(clean) | {"router", "experts"}
    assert read(drop_top=True)["logit_rms"] > limits["logit_rms"]
    assert clean["logit"] < read(router_dtype="bfloat16")["logit_rms"] \
        < limits["logit_rms"]
    int8, fp8 = (read(expert_dtype=t)["logit_rms"]
                 for t in ("int8", "float8_e4m3fn"))
    assert clean["logit_rms"] < int8 < fp8


def test_experts_stored_below_the_configurations_type():
    """``_stored_as``: one scale an output channel, so a channel's
    largest weight comes back exact; int8 within half a step of 1/127
    of it, float8_e4m3fn within 2^-4 of each weight."""
    w = np.random.default_rng(4).normal(size=(64, 48)).astype(np.float32)
    top = np.abs(w).max(0)
    assert (reference._stored_as(w, None) == w).all()
    int8 = np.asarray(reference._stored_as(w, "int8"))
    assert np.abs(int8 - w).max() <= (top / 127 / 2).max() * 1.0001
    fp8 = np.asarray(reference._stored_as(w, "float8_e4m3fn"))
    big = np.abs(w) > top / 448 * 2.0 ** 6  # no subnormal of the type
    assert (np.abs(fp8 - w)[big] <= np.abs(w)[big] * 2.0 ** -4).all()
    for got in (int8, fp8):
        np.testing.assert_allclose(np.abs(got).max(0), top, rtol=1e-6)
        assert 0 < np.abs(got - w).max()


def test_the_router_is_held_to_float32_on_identical_inputs(tiny):
    """(e): the program's router against the reference's on the same
    rows; a bfloat16 router on those rows swaps an expert or moves a
    weight by more than the ``router`` limit."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.moe import route_topk

    model, _weights, _fields = tiny
    router = model.layers[0].mlp.router._data
    m = jax.random.normal(jax.random.key(5), (256, 64), jnp.bfloat16)
    w, idx = route_topk(m, router, 2, True)
    err, rows = reference.compare_router(w, idx, m, router, 2, True)
    assert err < 1e-6 < reference.LIMITS["router"] and 200 < rows <= 256
    bad, _ = reference.compare_router(w, idx, m, router, 2, True,
                                      dtype=jnp.bfloat16)
    assert bad > reference.LIMITS["router"]
    # a dense [T, E] of the reference: top_k weights a row, summing to 1
    dense = np.asarray(reference.router_weights(m, router, 2, True))
    assert ((dense > 0).sum(-1) == 2).all()
    np.testing.assert_allclose(dense.sum(-1), 1.0, atol=1e-6)


def test_the_expert_layer_is_held_on_identical_inputs(tiny):
    """(f): the program's expert layer against the reference's experts
    on the same rows under the program's own routing: nothing on the
    layer's own float32 output, something once the reference keeps its
    experts in int8, more in float8, most with an expert dropped."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle

    model, weights, _fields = tiny
    layer, w = model.layers[1].mlp, weights[1][1]
    m = jax.random.normal(jax.random.key(6), (40, 64), jnp.float32)
    routed = []
    with paddle.no_grad():
        y = layer(paddle.to_tensor(np.asarray(m)), route_sink=routed)._data
    gate, idx = (t._data for t in routed[0])
    assert reference.compare_experts(y, m, gate, idx, w) < 1e-5
    int8, fp8, dropped = (
        reference.compare_experts(y, m, gate, idx, w, **fault)
        for fault in ({"expert_dtype": "int8"},
                      {"expert_dtype": "float8_e4m3fn"},
                      {"drop_top": True}))
    assert 2e-3 < int8 < fp8 < dropped
    # a row handed another row's output: the scatter's fault
    swapped = jnp.asarray(y).at[0].set(y[1]).at[1].set(y[0])
    assert reference.compare_experts(swapped, m, gate, idx, w) \
        > reference.LIMITS["experts"]


# -- the command end to end, in rehearsal ------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_block_diffusion_driver_end_to_end(
        trace, capsys, tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    rc = harness.main(["--rehearse", MANIFEST, "--workload", CELL,
                       "--seed", str(2**31 + 19), "--seconds", "1.5",
                       "--trace", str(trace)])
    assert rc == 0
    notes, last = (json.loads(ln) for ln in
                   capsys.readouterr().out.strip().splitlines()[-2:])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    # a rehearsal writes no number under a device metric's name
    assert last["metrics"] == {} and "breakdown" not in last
    cell = harness.load_cell(MANIFEST, CELL)
    if trace:
        # what needs no device trace is read from the counters
        assert {"forwards_per_block.sdar", "moe_rows_per_expert.sdar",
                "decoded_per_step.sdar", "sched_step_mean_ms.sdar",
                "kv_used_share.sdar", "kv_donated_share.sdar"} \
            <= set(last["rehearsal"]["would_report"]) \
            <= {m["name"] for m in cell.per_layer}
    else:
        assert set(last["rehearsal"]["would_report"]) == \
            {"serve_tok_s", "setup_s"}
    notes = notes["notes"]
    ref = notes["reference"]
    assert ref["ok"] and ref["rule_ok"] and ref["forwards"] >= 12
    # the check ran with the other slots live (4 slots: 2 check requests
    # beside 2 others), each layer's router read out of the block steps
    assert ref["load"]["live_slots_min"] >= 3
    assert ref["load"]["tile_rows"] == 16
    assert 0 < ref["load"]["largest_group_min"] \
        <= ref["load"]["largest_group_max"]
    assert ref["router_rows"] > 0 and len(ref["prompt_tokens"]) == 2
    # a rehearsal reads the planted faults as a traced run does
    assert set(ref["planted"]) == {"bf16_router", "dropped_expert",
                                   "int8_experts"}
    assert ref["planted"]["bf16_router"]["breaks"] == ["router"]
    assert {"logit_rms", "experts"} \
        <= set(ref["planted"]["dropped_expert"]["breaks"])
    assert ref["worst"]["experts"] < ref["planted"]["int8_experts"][
        "experts"]
    assert ref["limits"] == reference.LIMITS
    assert set(ref["limits"]) <= set(ref["worst"])
    assert notes["step"]["steps"] > 0 and notes["step"]["step_ms"] > 0
    route = notes["kernel_route"]
    assert route["serving.kernel.moe_gmm.pallas"] > 0
    assert route["serving.kernel.moe_gmm.plain"] == 0
    assert route["serving.kernel.pallas"] > 0
    assert route["serving.kernel.dense"] == 0
    assert "itl_ms" not in notes  # tokens arrive a block at a time
