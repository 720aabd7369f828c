"""A rehearsal of the latent cell (``xing29b-reasoning-saturated``) on the
CPU: the command end to end on a fixture manifest of its own
(``fixtures/latent/``: a tiny ``xing4_0`` configuration, mix and
workload), the new readers on a synthetic trace and counter maps
reckoned by hand, the operation counts at the published widths, and the
plain reference against itself. All in this process; nothing here
touches a TPU topology.

The synthetic slice is 30 us. Device operations: a fusion 0-1; a decode
step 2-12 (``mla_decode`` 2-3, 5-6 and 8-9; the tagged expert pair
``moe_gmm_swiglu_decode`` 6-7 + ``moe_gmm_decode`` 7-8 and 9-10 + 10-11;
fusions 3-5 and 11-12); a decode step 14-21 (``mla_decode`` 14-15, 16-17,
18-19; the pair 17-17.5 + 17.5-18 and 19-19.5 + 19.5-20; fusions 15-16
and 20-21); a prefill 23-27 (the untagged pair 23-24 + 24-25, a fusion
25-27); an extend's fusion 29-30: 23 us busy.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, ops_count_mla, ops_count_moe  # noqa: E402
from benchmarks import trace_reduce  # noqa: E402
from benchmarks.reference import latent_moe_hc as reference  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "latent")
MANIFEST = os.path.join(FIXTURES, "BENCHMARK.json")
CELL = "tiny-latent"
REAL_CELL = "xing29b-reasoning-saturated"
US = 1e-6
NEW_FAMILIES = ("mla_attn_busy_share", "mla_attn_hbm_roofline",
                "mla_step_mfu", "mla_step_hbm_roofline",
                "sparse_gmm_hbm_roofline")

_OPS = {1: '%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p.1), '
           'kind=kLoop',
        2: '%mla_decode.3 = bf16[4,4,128]{2,1,0} custom-call(s32[4,8]{1,0} '
           '%tables.1), custom_call_target=\\"tpu_custom_call\\"',
        3: '%moe_gmm_swiglu_decode.4 = bf16[64,24]{1,0} custom-call(s32[4]'
           '{0} %te.1), custom_call_target=\\"tpu_custom_call\\"',
        4: '%moe_gmm_decode.5 = bf16[64,32]{1,0} custom-call(s32[4]{0} '
           '%te.1), custom_call_target=\\"tpu_custom_call\\"',
        5: '%moe_gmm_swiglu.6 = bf16[64,24]{1,0} custom-call(s32[4]{0} '
           '%te.1), custom_call_target=\\"tpu_custom_call\\"',
        6: '%moe_gmm.7 = bf16[64,32]{1,0} custom-call(s32[4]{0} %te.1), '
           'custom_call_target=\\"tpu_custom_call\\"'}
_MODULES = {11: "jit_xing_paged_decode(1111111111)",
            12: "jit_xing_paged_prefill(2222222222)",
            13: "jit_xing_paged_extend(3333333333)"}
# (operation, start us, length us)
_EVENTS = [(1, 0, 1),
           (2, 2, 1), (1, 3, 2), (2, 5, 1), (3, 6, 1), (4, 7, 1), (2, 8, 1),
           (3, 9, 1), (4, 10, 1), (1, 11, 1),
           (2, 14, 1), (1, 15, 1), (2, 16, 1), (3, 17, .5), (4, 17.5, .5),
           (2, 18, 1), (3, 19, .5), (4, 19.5, .5), (1, 20, 1),
           (5, 23, 1), (6, 24, 1), (1, 25, 2), (1, 29, 1)]
_MODULE_EVENTS = [(11, 0, 1), (11, 2, 10), (11, 14, 7), (12, 23, 4),
                  (13, 29, 1)]


def _trace_text():
    def meta(table):
        return "\n".join(
            f'  event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}'
            for k, v in table.items())

    def events(rows):
        return "\n".join(
            f"    events {{ metadata_id: {k} offset_ps: {int(s * 1e6)} "
            f"duration_ps: {int(d * 1e6)} }}" for k, s, d in rows)

    return f"""
planes {{ id: 1 name: "/device:TPU:0"
{meta(_OPS)}
{meta(_MODULES)}
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000
{events(_EVENTS)}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000000
{events(_MODULE_EVENTS)}
  }}
}}
planes {{ id: 2 name: "/host:CPU"
  event_metadata {{ key: 1 value {{ id: 1 name: "serving.step" }} }}
  event_metadata {{ key: 7 value {{ id: 7 name: "serving.decode.dispatch" }} }}
  event_metadata {{ key: 8 value {{ id: 8 name: "serving.decode.readback" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "batch" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "context_tokens" }} }}
  lines {{ id: 7 name: "python3" timestamp_ns: 1000000
    events {{ metadata_id: 1 offset_ps: 1000000 duration_ps: 11500000 }}
    events {{ metadata_id: 7 offset_ps: 1500000 duration_ps: 1000000 stats {{ metadata_id: 2 int64_value: 3 }} stats {{ metadata_id: 3 int64_value: 300 }} }}
    events {{ metadata_id: 8 offset_ps: 2500000 duration_ps: 9500000 }}
    events {{ metadata_id: 1 offset_ps: 13600000 duration_ps: 8000000 }}
    events {{ metadata_id: 7 offset_ps: 13700000 duration_ps: 800000 stats {{ metadata_id: 2 int64_value: 3 }} stats {{ metadata_id: 3 int64_value: 500 }} }}
    events {{ metadata_id: 8 offset_ps: 14500000 duration_ps: 6500000 }}
  }}
}}
"""


def _json(path):
    with open(path) as f:
        return json.load(f)


class _Cell:
    name = "synthetic-latent"
    config = _json(os.path.join(FIXTURES, "configs", "tiny-xing.json"))
    traffic = _json(os.path.join(FIXTURES, "traffic", "tiny-reasoning.json"))


def _hist(count, total):
    return {"count": count, "sum": total}


# ten decode steps of 3 live slots over 400 context tokens each: 2 sparse
# layers x 2 experts a token x 3 rows = 12 routed rows a step, which hit
# 5 experts a sparse layer
_COUNTERS = {
    "serving.phase.decode_dispatch_us": _hist(10, 9000.0),
    "serving.phase.prefill_forward_us": _hist(4, 8000.0),
    "serving.decode.context_tokens": 4000,
    "serving.moe.rows": 120, "serving.moe.experts_hit": 100,
    "serving.moe.max_rows": 40}
# of the fixture's configuration (hidden 32, 4 heads of 16 + 8 over a
# latent of 32, q rank 24, v 16; 8 experts of 24, 2 a token, 1 shared; a
# dense width of 64; 4 streams; 1 dense + 2 sparse layers; vocabulary 256)
_MLA = 32 * 24 + 24 * 4 * 24 + 32 * 40 + 32 * 4 * 32 + 4 * 16 * 32
_MAPS = 2 * (4 * 32) * (4 * 6)
_EXPERT = 3 * 32 * 24
_DENSE = _MLA + _MAPS + 3 * 32 * 64
_SPARSE = _MLA + _MAPS + 32 * 8 + 3 * _EXPERT
_HEAD = 32 * 256


@pytest.fixture()
def ctx(tmp_path, monkeypatch):
    """A readers' ctx whose cell's trace is the synthetic one: written
    where the harness writes a cell's trace, under a TRACE_DIR of the
    test's own."""
    from jax.profiler import ProfileData

    text = _trace_text()
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    where = tmp_path / _Cell.name / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    planes = ProfileData.from_text_proto(text).planes
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
    assert cell.workload["driver"] == "serve_latent" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    assert {f + ".serve" for f in NEW_FAMILIES} <= set(mine)
    # the families whose count would be wrong here are not reported
    families = {n.split(".")[0] for n in mine}
    assert not families & {"paged_attn_busy_share",
                           "paged_attn_hbm_roofline",
                           "moe_gmm_hbm_roofline", "decode_step_mfu",
                           "decode_step_hbm_roofline"}
    for name, layer, better in (
            ("mla_attn_hbm_roofline.serve", "kernels", "higher"),
            ("sparse_gmm_hbm_roofline.serve", "kernels", "higher"),
            ("mla_attn_busy_share.serve", "kernels", "lower"),
            ("mla_step_mfu.serve", "model step", "higher"),
            ("mla_step_hbm_roofline.serve", "model step", "higher")):
        assert (mine[name]["layer"], mine[name]["better"],
                mine[name]["unit"], mine[name]["source"]) \
            == (layer, better, "%", "device_trace")
    # what the accepted benchmark had is as it was: entries were appended
    # behind the Jamba cell's (a later cell goes behind these in turn, so
    # nothing here pins the lists' ends)
    cells = [w["name"] for w in real["workloads"]]
    assert cells.index(REAL_CELL) == cells.index(
        "jamba3b-reasoning-saturated") + 1
    configs = [c["name"] for c in real["configs"]]
    assert configs.index("xing4.0-29b-a4b") == configs.index(
        "ai21-jamba2-3b") + 1
    assert real["configs"][configs.index("xing4.0-29b-a4b")]["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"]
    served = next(m for m in real["end_to_end"]
                  if m["name"] == "serve_tok_s")["workloads"]
    assert served.index(REAL_CELL) == served.index(
        "jamba3b-reasoning-saturated") + 1
    assert len(real["workloads"][cells.index(REAL_CELL)]["why"]) <= 200


def test_the_real_cell_is_the_issues_traffic_and_configuration():
    cell = harness.load_cell(harness.MANIFEST, REAL_CELL)
    t, e = cell.traffic, cell.workload["engine"]
    # the file the Jamba cell uses, as it is
    assert t == harness.load_cell(harness.MANIFEST,
                                  "jamba3b-reasoning-saturated").traffic
    assert (t["loop"], t["clients"], t["cycle"]) == ("closed", 256, 256)
    assert e == {"slots": 128, "block_size": 16, "max_seq_len": 4096,
                 "bucket_cap": 1024}
    from paddle_tpu.core import flags
    assert e["bucket_cap"] == flags.flag("FLAGS_serving_prefill_bucket_cap")
    c = cell.config
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert c["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if c.get(k) != v}
        assert differs == set(c["reduced"])    # the catalog row, key for key
    # every published width as it is
    widths = {"hidden_size": 3584, "num_attention_heads": 32,
              "q_lora_rank": 768, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "intermediate_size": 9216,
              "n_routed_experts": 64, "moe_intermediate_size": 1024,
              "num_experts_per_tok": 4, "n_shared_experts": 1,
              "vocab_size": 131072, "hc_mult": 4, "hc_sinkhorn_iters": 20}
    assert {k: c[k] for k in widths} == widths
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["num_nextn_predict_layers"]) == (6, 1, 0)
    assert set(c["reduced"]) == {"num_hidden_layers",
                                 "first_k_dense_replace",
                                 "num_nextn_predict_layers"}
    assert {"initializer_range", "hyper_connections",
            "hyper_connection_init", "yarn", "rotary_pairing",
            "router_bias"} <= set(c["assumed"])
    assert "stage 1 of 8" in c["deployment"]
    assert c["torch_dtype"] == "bfloat16"
    # the driver builds the model's configuration from these keys
    driver = harness.load_module(cell.driver_path)
    built = driver.xing_config(c)
    assert (built.num_layers, built.first_k_dense_replace, built.num_heads,
            built.kv_lora_rank, built.hc_mult) == (6, 1, 32, 512, 4)
    assert built.rope_scaling["factor"] == 64
    assert abs(built.softmax_scale - 0.14468) < 1e-5


def test_the_real_cell_serves_one_set_of_weights_on_every_seed(monkeypatch):
    cell = harness.load_cell(harness.MANIFEST, REAL_CELL)
    driver = harness.load_module(cell.driver_path)
    fixed = cell.workload["weights_seed"]
    assert isinstance(fixed, int) and 0 <= fixed < 2 ** 64
    assert {driver.weights_seed(cell.workload, s)
            for s in (1, 2 ** 31 + 23, 3910003003)} == {fixed}
    # a workload that names no key draws the weights from the run's seed
    fixture = harness.load_cell(MANIFEST, CELL)
    assert driver.weights_seed(fixture.workload, 7) == 7

    class Built(Exception):
        pass

    def build(_cls, _config, _dtype, seed):
        raise Built(seed)

    # the key the run's driver hands the builder is the workload's
    monkeypatch.setattr(driver, "build_model", build)
    with pytest.raises(Built) as got:
        driver.run(harness.Run(cell, 2 ** 31 + 23, 1.0, False, True, None))
    assert got.value.args == (fixed,)


# -- the counts ------------------------------------------------------------------

def test_operation_counts_at_the_published_widths():
    fields = harness.load_cell(harness.MANIFEST, REAL_CELL).config
    s = ops_count_mla.shapes(fields)
    assert (s["dense_layers"], s["sparse_layers"]) == (1, 5)
    mla = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
    assert ops_count_mla.mla_params(fields) == mla
    assert round(mla / 1e6, 2) == 28.41
    assert ops_count_mla.stream_map_params(fields) == 2 * 14336 * 24
    assert ops_count_mla.attention_bytes_per_token(fields) == 1152
    assert ops_count_mla.attention_flops_per_token(fields) \
        == 32 * (2 * 576 + 2 * 512) == 69632
    assert ops_count_mla.attention_call_bytes(fields, 166_400) \
        == 166_400 * 1152
    expert = 3 * 3584 * 1024
    dense, sparse, head = ops_count_mla.params_a_row_multiplies(fields)
    assert dense == mla + 2 * 14336 * 24 + 3 * 3584 * 9216
    assert sparse == mla + 2 * 14336 * 24 + 3584 * 64 + 5 * expert
    assert head == 3584 * 131072
    # the whole model: 4792.6M with the embedding (looked up, not read)
    whole = dense + 5 * (sparse + 60 * expert) + 2 * head
    assert abs(whole - 4792.6e6) < 0.5e6
    # a step of 128 rows over 166.4k context tokens, every expert hit:
    # 8.65 GB of weights + 1.15 GB of rows
    step = ops_count_mla.decode_step_bytes(fields, 166_400, 64)
    weights = (head + 6 * (mla + 2 * 14336 * 24) + 3 * 3584 * 9216
               + 5 * (3584 * 64 + 65 * expert)) * 2
    assert step == weights + 166_400 * 1152 * 6
    assert 8.60e9 < weights < 8.70e9 and 1.14e9 < step - weights < 1.16e9
    flops = ops_count_mla.decode_step_flops(fields, 128, 166_400)
    assert flops == 2 * 128 * (dense + 5 * sparse + head) \
        + 69632 * 166_400 * 6
    assert 0.32e12 < flops < 0.34e12          # bound by bytes: 1.7 ms of MXU
    # fewer experts hit read fewer bytes; the rows alone move none
    assert ops_count_mla.decode_step_bytes(fields, 166_400, 32) \
        == step - 5 * 32 * expert * 2
    assert ops_count_moe.expert_layer_bytes(64, 512, fields, 2) \
        == 64 * expert * 2 + 512 * 2 * (3584 + 1024) * 2


# -- the readers on the synthetic trace ------------------------------------------

def test_the_kernels_shares_on_the_synthetic_trace(ctx):
    assert ctx["trace"]["busy_s"] == pytest.approx(23 * US)
    assert _read("mla_attn_busy_share.x", ctx) == pytest.approx(
        100 * 6 / 23)
    # the tagged pairs and the prefill's untagged one: 6 + 2 us of 23
    assert _read("moe_gmm_busy_share.x", ctx) == pytest.approx(
        100 * 8 / 23)
    # 400 context tokens a dispatch x 80 bytes a row in a 1 us call
    want = 100 * 400 * 80 / 1e-6 / 819e9
    assert _read("mla_attn_hbm_roofline.x", ctx) == pytest.approx(want)
    assert 0 < want < 100
    # 5 experts hit and 6 rows a sparse layer of a step; four tagged pairs
    # of 6 us together; the prefill's pair is not the decode step's
    nbytes = ops_count_moe.expert_layer_bytes(5, 6, _Cell.config, 2)
    assert nbytes == (5 * _EXPERT + 6 * 2 * (32 + 24)) * 2
    want = 100 * nbytes / 1.5e-6 / 819e9
    assert _read("sparse_gmm_hbm_roofline.x", ctx) == pytest.approx(want)
    assert 0 < want < 100
    assert _read("moe_rows_per_expert.x", ctx) == pytest.approx(1.2)


def test_the_whole_steps_shares_on_the_synthetic_trace(ctx):
    # the steps of 10 and 7 us lie inside the slice; the one its edge
    # cuts is left out. 3 rows, 400 context tokens and 5 experts hit a
    # sparse layer a step
    assert ops_count_mla.mla_params(_Cell.config) == _MLA == 10496
    assert ops_count_mla.params_a_row_multiplies(_Cell.config) \
        == (_DENSE, _SPARSE, _HEAD)
    flops = 2 * 3 * (_DENSE + 2 * _SPARSE + _HEAD) + 576 * 400 * 3
    want = 100 * flops / 8.5e-6 / 197e12
    assert _read("mla_step_mfu.x", ctx) == pytest.approx(want)
    assert 0 < want < 100
    nbytes = (_HEAD + 3 * (_MLA + _MAPS) + 3 * 32 * 64
              + 2 * (32 * 8 + 6 * _EXPERT)) * 2 + 80 * 400 * 3
    want = 100 * nbytes / 8.5e-6 / 819e9
    assert _read("mla_step_hbm_roofline.x", ctx) == pytest.approx(want)
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

def test_the_reference_is_causal_and_its_faults_move_it():
    """Padding past the end changes nothing before it, the head a block
    of the vocabulary at a time is the head, and each planted fault
    moves the logits."""
    import paddle_tpu as paddle
    from paddle_tpu.models import Xing

    cell = harness.load_cell(MANIFEST, CELL)
    driver = harness.load_module(cell.driver_path)
    paddle.seed(0)
    model = Xing(driver.xing_config(cell.config))
    model.eval()
    weights = reference.weights_of(model)
    fields = reference.fields_of(cell.config)
    ids = np.random.default_rng(0).integers(3, 250, size=300)
    logits, cached = reference.forward(weights, fields, ids,
                                       np.arange(280, 300))
    assert logits.shape == (20, 256) and len(cached) == 3
    assert cached[0][0].shape == (300, 32) and cached[0][1].shape == (300, 8)
    part, part_rows = reference.forward(weights, fields, ids[:256],
                                        np.arange(250, 256))
    again, _ = reference.forward(weights, fields, ids, np.arange(250, 256))
    np.testing.assert_allclose(part, again, atol=1e-5)
    np.testing.assert_allclose(part_rows[2][0], cached[2][0][:256],
                               atol=1e-5)
    for name, kw in reference.FAULTS.items():
        if name == "other_maps":     # a fault of the replay alone
            continue
        bad, _ = reference.forward(weights, fields, ids,
                                   np.arange(280, 300), **kw)
        assert np.abs(bad - logits).max() > 1e-6 * np.abs(logits).max(), name
    assert reference.rel_rms(logits, logits) == 0.0
    quiet = reference.quiet_rms(np.array([1.0, 0.1, -2.0, 0.0]),
                                np.array([1.0, 0.2, -2.0, 0.1]))
    assert quiet == pytest.approx(np.sqrt(0.01 / (5.05 / 4)))


# -- the command end to end, in rehearsal ------------------------------------------------

def test_rehearsal_runs_the_latent_driver_end_to_end(capsys, tmp_path,
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
    assert {"decoded_per_step.serve", "sched_step_mean_ms.serve",
            "kv_used_share.serve", "kv_donated_share.serve",
            "decode_ahead_share.serve", "host_ms_per_step.serve",
            "moe_rows_per_expert.serve"} \
        <= set(last["rehearsal"]["would_report"]) \
        <= {m["name"] for m in cell.per_layer}
    notes = notes["notes"]
    ref = notes["reference"]
    assert ref["ok"] and ref["tokens_each"] == 24
    # the check ran with the other slots live (4 slots: 2 check requests
    # beside 2 others)
    assert ref["load"]["live_slots_min"] >= 3
    assert len(ref["prompt_tokens"]) == 2
    assert len(ref["tapped_positions"]) == 3
    assert ref["limits"] == reference.LIMITS
    assert all(ref["worst"][k] <= v for k, v in ref["limits"].items())
    assert set(ref["limits"]) | {"read"} == set(ref["worst"])
    # float32 on both sides of the maps and of the router
    assert ref["worst"]["mix"] < 1e-5 and ref["worst"]["router"] < 1e-5
    # a rehearsal reads the planted faults, each in
    # the reading that is there for it (YaRN's factor of the scale is
    # seen at the published factor of 64, not at the fixture's 8)
    assert set(ref["planted"]) == set(reference.FAULTS) | {
        "no_shared_full", "other_head_full"}
    for fault, reading in (("int8_rows", "row"), ("bf16_router", "router"),
                           ("no_shared", "experts"), ("no_bias", "router"),
                           ("other_head", "attn"), ("other_maps", "mix")):
        assert reading in ref["planted"][fault]["breaks"], fault
    # the limits are the published widths': 14336 stream values make the
    # Sinkhorn problem harder than the fixture's 128, a factor of 64 moves
    # the scale more than the fixture's 8. Here both still stand out
    assert ref["planted"]["sinkhorn_5"]["mix"] > 100 * ref["worst"]["mix"]
    assert ref["planted"]["no_yarn_scale"]["attn"] > 2 * ref["worst"]["attn"]
    assert set(ref["margins"]) == {"p50", "p75", "p90", "p99", "p100",
                                   "over_margin", "logit_rms_steps"}
    assert min(ref["margins"]["logit_rms_steps"]) \
        == ref["worst"]["logit_rms"]
    assert "logit_rms" in ref["planted"]["other_head_full"]["breaks"]
    assert notes["step"]["steps"] > 0 and notes["step"]["step_ms"] > 0
    # 3 layers x 33 blocks x 8 tokens x (32 + 128 lanes) x 2 bytes
    assert notes["latent_bytes"] == 3 * 33 * 8 * 160 * 2
    assert notes["moe"]["rows"] > 0 and notes["decode"]["ahead"] > 0
    route = notes["kernel_route"]
    for kernel in ("mla_decode", "moe_gmm"):
        assert route[f"serving.kernel.{kernel}.pallas"] > 0
        assert route[f"serving.kernel.{kernel}.plain"] == 0
    assert notes["ended"]["preempt"] == 0 and not notes["failures"]
