"""What each accepted cell reads, frozen, and a later cell that is taken
without an edit.

The manifest holds one per-layer entry a family and moved metric: the tag
names the end-to-end metric the entry moves (``.serve`` for
``serve_tok_s``, ``.steady`` for ``itl_p95_ms``, ``.train`` for
``train_tok_s``) and its ``workloads`` list every accepted cell that
reads it. A later cell appends its configuration, its cell and entries
of its own under a tag of its own, and edits none. The second half of
this file does that to a copy of the real manifest (the files of
``fixtures/later/`` are the hybrid fixture's, copied under new names)
and runs every accepted test that reads the real manifest on the copy.
All on the CPU, in this process.
"""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from benchmarks import harness  # noqa: E402

TAGS = {"serve_tok_s": "serve", "itl_p95_ms": "steady",
        "train_tok_s": "train"}
# the serving families every served model's cell reads
GENERIC = """window_compiles decoded_per_step sched_step_mean_ms
    prefill_ms_per_step decode_ms_per_step host_ms_per_step
    decode_ahead_share kv_used_share kv_donated_share
    pool_write_ms_per_prefill device_idle_share idle_in_prefill_share
    idle_in_decode_share idle_in_host_share idle_unowned_share
    hbm_peak_gb""".split()
SCOPES = """busy_in_attn_share busy_in_ffn_share busy_in_mixer_share
    busy_in_residual_share busy_in_head_share busy_unscoped_share""".split()
# the end-to-end metric each accepted cell's per-layer metrics move, and
# their families
READS = {
    "mistral7b-batch-saturated": ("serve_tok_s", GENERIC + SCOPES + """
        paged_attn_busy_share paged_attn_hbm_roofline
        prefill_busy_share""".split()),
    "gpt2m-train-seq1024": ("train_tok_s", """step_ms mfu window_compiles
        flash_roofline device_idle_share hbm_peak_gb step_device_ms
        busy_in_attn_share busy_in_ffn_share busy_in_residual_share
        busy_in_head_share busy_in_optimizer_share
        busy_unscoped_share""".split()),
    "mistral7b-chat-steady": ("itl_p95_ms", """gen_late_p95_ms
        window_compiles queue_wait_mean_ms sched_step_mean_ms
        device_idle_share prefill_ms_per_step decode_ms_per_step
        host_ms_per_step idle_in_prefill_share idle_in_decode_share
        idle_in_host_share idle_unowned_share prefill_busy_share
        kv_donated_share decode_ahead_share busy_in_attn_share
        busy_in_ffn_share busy_in_head_share
        busy_unscoped_share""".split()),
    "sdar30b-blockdiff-saturated": ("serve_tok_s", GENERIC + SCOPES + """
        forwards_per_block moe_rows_per_expert moe_gmm_busy_share
        moe_gmm_hbm_roofline block_attn_hbm_roofline block_attn_busy_share
        blockdiff_prefill_busy_share step_mfu""".split()),
    "jamba3b-reasoning-saturated": ("serve_tok_s", GENERIC + SCOPES + """
        paged_attn_busy_share paged_attn_hbm_roofline ssm_update_busy_share
        ssm_update_hbm_roofline ssm_scan_busy_share ssm_scan_hbm_roofline
        hybrid_prefill_busy_share decode_step_mfu
        decode_step_hbm_roofline""".split()),
    "xing29b-reasoning-saturated": ("serve_tok_s", GENERIC + SCOPES + """
        moe_rows_per_expert moe_gmm_busy_share mla_attn_busy_share
        mla_attn_hbm_roofline mla_step_mfu mla_step_hbm_roofline
        sparse_gmm_hbm_roofline""".split()),
}
# unit and better of each family, whichever metric it moves
KINDS = {
    ("%", "lower"): """paged_attn_busy_share device_idle_share
        idle_in_prefill_share idle_in_decode_share idle_in_host_share
        idle_unowned_share prefill_busy_share moe_gmm_busy_share
        blockdiff_prefill_busy_share block_attn_busy_share
        ssm_update_busy_share ssm_scan_busy_share hybrid_prefill_busy_share
        mla_attn_busy_share busy_in_optimizer_share""".split() + SCOPES,
    ("%", "higher"): """kv_used_share mfu flash_roofline
        paged_attn_hbm_roofline kv_donated_share moe_gmm_hbm_roofline
        block_attn_hbm_roofline step_mfu decode_ahead_share
        ssm_update_hbm_roofline ssm_scan_hbm_roofline decode_step_mfu
        decode_step_hbm_roofline mla_attn_hbm_roofline mla_step_mfu
        mla_step_hbm_roofline sparse_gmm_hbm_roofline""".split(),
    ("ms", "lower"): """gen_late_p95_ms queue_wait_mean_ms
        sched_step_mean_ms step_ms prefill_ms_per_step decode_ms_per_step
        host_ms_per_step pool_write_ms_per_prefill
        step_device_ms""".split(),
    ("count", "lower"): ["window_compiles", "forwards_per_block"],
    ("count", "higher"): ["moe_rows_per_expert"],
    ("tokens/step", "higher"): ["decoded_per_step"],
    ("GB", "lower"): ["hbm_peak_gb"],
}
KIND = {family: kind for kind, families in KINDS.items()
        for family in families}


def _json(path):
    with open(path) as f:
        return json.load(f)


def _reads(manifest_path, cell):
    loaded = harness.load_cell(manifest_path, cell)
    return {(m["name"].split(".")[0],
             os.path.relpath(harness.reader_path(m["name"]), REPO),
             m["moves"], m["unit"], m["better"]) for m in loaded.per_layer}


@pytest.mark.parametrize("cell", list(READS))
def test_an_accepted_cell_reads_what_it_read(cell):
    """The set of (family, reader, moved metric, unit, better) each cell
    loads; the names are the only thing the merge of per-cell entries
    into one entry a family changed, and the block-diffusion cell's
    ``decode_ahead_share`` the one thing it added."""
    moves, families = READS[cell]
    assert len(set(families)) == len(families)
    want = {(f, f"benchmarks/layer_metrics/{f}.py", moves) + KIND[f]
            for f in families}
    assert _reads(harness.MANIFEST, cell) == want


def test_one_entry_a_family_and_moved_metric():
    manifest = _json(harness.MANIFEST)
    assert harness.manifest_problems(manifest) == []
    assert len(manifest["per_layer"]) <= 128
    accepted = [m for m in manifest["per_layer"]
                if set(m.get("workloads", READS)) & set(READS)]
    assert len(accepted) == 77
    keys = [(m["name"].split(".")[0], m["moves"]) for m in accepted]
    assert len(set(keys)) == len(keys)
    cells = [w["name"] for w in manifest["workloads"]]
    for m in accepted:
        # named for the metric it moves, and listing its cells in the
        # manifest's order
        assert m["name"].split(".")[1] == TAGS[m["moves"]]
        assert m["workloads"] == sorted(m["workloads"], key=cells.index)
        assert all(READS[w][0] == m["moves"] for w in m["workloads"])


# -- a later cell -----------------------------------------------------------

LATER = "tiny-later-serve"
LATER_FIXTURES = "tests/benchmark_harness/fixtures/later"
# what a served model of a new kind brings beside the generic families:
# families that have a reader already, under the later cell's tag
LATER_OWN = """ssm_update_busy_share ssm_update_hbm_roofline
    ssm_scan_hbm_roofline hybrid_prefill_busy_share decode_step_mfu
    decode_step_hbm_roofline moe_rows_per_expert
    busy_in_mixer_share""".split()


def _with_a_later_cell(manifest):
    """``manifest`` with a seventh serving cell appended as a model's PR
    adds one: a configuration, a cell, the cell under ``serve_tok_s``, and
    24 entries of its own. Nothing that was there is changed."""
    manifest["configs"].append({
        "name": "tiny-later",
        "source": "fixture: the ai21-jamba2-3b keys at toy widths, for "
                  "CPU rehearsal only",
        "file": LATER_FIXTURES + "/configs/tiny-later.json",
        "reduced": [], "why": "a later configuration, in rehearsal"})
    manifest["workloads"].append({
        "name": LATER, "config": "tiny-later",
        "traffic": "tiny-later-reasoning", "chips": 1,
        "why": "a later serving cell, in rehearsal"})
    next(m for m in manifest["end_to_end"]
         if m["name"] == "serve_tok_s")["workloads"].append(LATER)
    serve = {m["name"]: m for m in manifest["per_layer"]}
    manifest["per_layer"] += [
        dict(serve[f + ".serve"], name=f + ".later", workloads=[LATER])
        for f in GENERIC + LATER_OWN]
    return manifest


@pytest.fixture()
def later_manifest(tmp_path, monkeypatch):
    """A copy of the real manifest with the later cell, written where the
    tests that read the real manifest find it."""
    manifest = _with_a_later_cell(_json(harness.MANIFEST))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest, indent=1) + "\n")
    monkeypatch.setattr(harness, "MANIFEST", str(path))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    return str(path)


def test_a_later_cell_fits_beside_the_accepted_ones(later_manifest):
    manifest = _json(later_manifest)
    assert harness.manifest_problems(manifest) == []
    assert len(manifest["per_layer"]) <= 128
    mine = [m for m in manifest["per_layer"]
            if m.get("workloads") == [LATER]]
    assert len(mine) == 24
    assert manifest["per_layer"][:-24] \
        == _json(os.path.join(REPO, "BENCHMARK.json"))["per_layer"]


def _accepted(module, function, *args):
    def run():
        return getattr(importlib.import_module(module), function)(*args)
    shown = f"[{','.join(args)}]" if args else ""
    return pytest.param(run, id=f"{module}::{function}{shown}")


def _self_consistent():
    importlib.import_module("test_benchmark_harness") \
        .test_manifest_is_self_consistent(harness.MANIFEST)


# every accepted test that reads the real manifest, with its arguments
ACCEPTED = [
    pytest.param(_self_consistent,
                 id="test_benchmark_harness::test_manifest_is_self_"
                    "consistent"),
    _accepted("test_benchmark_harness",
              "test_real_manifest_keeps_the_contract_limits"),
    _accepted("test_benchmark_harness",
              "test_counter_readers_and_unknown_devices"),
    _accepted("test_blockdiff_cell",
              "test_the_fixture_manifest_mirrors_the_real_cell"),
    _accepted("test_blockdiff_cell",
              "test_the_real_cell_is_the_issues_traffic_and_engine_shape"),
    _accepted("test_blockdiff_cell",
              "test_operation_counts_at_the_published_widths"),
    _accepted("test_hybrid_cell",
              "test_the_fixture_manifest_mirrors_the_real_cell"),
    _accepted("test_hybrid_cell",
              "test_the_real_cell_is_the_issues_traffic_and_configuration"),
    _accepted("test_hybrid_cell",
              "test_operation_counts_at_the_published_widths"),
    _accepted("test_latent_cell",
              "test_the_fixture_manifest_mirrors_the_real_cell"),
    _accepted("test_latent_cell",
              "test_the_real_cell_is_the_issues_traffic_and_configuration"),
    _accepted("test_latent_cell",
              "test_operation_counts_at_the_published_widths"),
    _accepted("test_scope_metrics", "test_the_manifest_has_the_sixteen_entries"),
    _accepted("test_decode_ahead_share",
              "test_the_real_manifest_lists_decode_ahead_share_in_the_"
              "mistral_cells"),
    _accepted("test_kv_donated_share",
              "test_the_real_manifest_lists_kv_donated_share_in_the_"
              "serving_cells"),
    _accepted("test_span_metrics",
              "test_the_real_manifest_lists_the_new_metrics_where_they_read"),
    _accepted("test_accepted_cells", "test_one_entry_a_family_and_moved_metric"),
] + [
    _accepted("test_scope_metrics",
              "test_every_cell_reads_its_tags_shares_and_no_others", cell,
              TAGS[READS[cell][0]]) for cell in READS
] + [
    _accepted("test_accepted_cells", "test_an_accepted_cell_reads_what_it_read",
              cell) for cell in READS]


@pytest.mark.parametrize("accepted_test", ACCEPTED)
def test_an_accepted_invariant_holds_with_a_later_cell(later_manifest,
                                                       accepted_test):
    accepted_test()


def test_the_later_cell_rehearses_with_no_harness_file_edited(
        later_manifest, capsys):
    rc = harness.main(["--rehearse", later_manifest, "--workload", LATER,
                       "--seed", "1", "--seconds", "2", "--trace", "1"])
    assert rc == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0 and last["metrics"] == {}
    cell = harness.load_cell(later_manifest, LATER)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tok_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names == {f + ".later" for f in GENERIC + LATER_OWN}
    # what needs no device trace is read from the counters
    assert {"decoded_per_step.later", "sched_step_mean_ms.later",
            "kv_used_share.later", "kv_donated_share.later",
            "decode_ahead_share.later", "host_ms_per_step.later"} \
        <= set(last["rehearsal"]["would_report"]) <= names
