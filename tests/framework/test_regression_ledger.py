"""Continuous-bench regression ledger: tools/bench_ledger.py append/
read round-trip + tools/regression_gate.py median comparison,
direction/tolerance policy, synthetic-regression self-test, and the
suite_gate advisory hook.

All against temp-dir ledgers — the real BENCH_LEDGER.jsonl is never
touched by tests.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools"))

import bench_ledger  # noqa: E402
import regression_gate  # noqa: E402


@pytest.fixture()
def ledger(tmp_path):
    return str(tmp_path / "ledger.jsonl")


# -- the ledger ---------------------------------------------------------


def test_append_read_roundtrip(ledger):
    e = bench_ledger.append_entry("bench", {"tokens_per_s": 100.0},
                                  path=ledger, meta={"note": "x"})
    assert e["kind"] == "bench" and e["git_sha"]
    bench_ledger.append_entry("bench", {"tokens_per_s": 110.0},
                              path=ledger)
    got = bench_ledger.entries(ledger)
    assert len(got) == 2                       # append-only: both lines
    assert got[0]["metrics"]["tokens_per_s"] == 100.0
    assert got[1]["metrics"]["tokens_per_s"] == 110.0
    assert got[0]["ts"] <= got[1]["ts"]
    assert got[0]["meta"] == {"note": "x"}
    with open(ledger) as f:
        assert len(f.read().strip().splitlines()) == 2


def test_kind_filter_and_last(ledger):
    for i in range(5):
        bench_ledger.append_entry("a", {"v": float(i)}, path=ledger)
    bench_ledger.append_entry("b", {"v": 99.0}, path=ledger)
    assert len(bench_ledger.entries(ledger, kind="a")) == 5
    assert len(bench_ledger.entries(ledger, kind="b")) == 1
    tail = bench_ledger.last(2, "a", ledger)
    assert [e["metrics"]["v"] for e in tail] == [3.0, 4.0]


def test_malformed_lines_skipped(ledger):
    bench_ledger.append_entry("a", {"v": 1.0}, path=ledger)
    with open(ledger, "a") as f:
        f.write("{truncated by a crash\n")
        f.write('"not a dict"\n')
        f.write(json.dumps({"no_metrics": True}) + "\n")
    bench_ledger.append_entry("a", {"v": 2.0}, path=ledger)
    got = bench_ledger.entries(ledger)
    assert [e["metrics"]["v"] for e in got] == [1.0, 2.0]


def test_missing_ledger_is_empty(tmp_path):
    assert bench_ledger.entries(str(tmp_path / "nope.jsonl")) == []


# -- the regression gate ------------------------------------------------


def test_direction_policy():
    assert regression_gate.direction_and_tol("serve_mean_step_ms")[0] \
        == "up"
    assert regression_gate.direction_and_tol("warm_ttft_us")[0] == "up"
    assert regression_gate.direction_and_tol(
        "headline_tokens_per_s") == ("down",
                                     regression_gate.HEADLINE_TOL)
    assert regression_gate.direction_and_tol("headline_mfu")[0] == "down"
    assert regression_gate.direction_and_tol("prefix_hit_rate")[0] \
        == "down"
    # counts/config echoes are recorded but never judged
    assert regression_gate.direction_and_tol("suite_targets") is None
    # the success sentinel IS judged: any drop below the 1.0 median fails
    assert regression_gate.direction_and_tol("serve_done") == ("down", 0.0)
    history = [{"serve_done": 1.0}] * 5
    regs, _ = regression_gate.compare({"serve_done": 0.0}, history)
    assert [r["metric"] for r in regs] == ["serve_done"]
    regs, _ = regression_gate.compare({"serve_done": 1.0}, history)
    assert not regs


def test_mesh_serve_direction_policy():
    """PR 15 satellite: the mesh_serve rung's tokens/s and
    tokens/s/device ride the EXISTING down-is-worse rate rules (the
    `_per_s` suffix) — no bespoke policy to rot."""
    assert regression_gate.direction_and_tol("mesh_d8_tokens_per_s") \
        == ("down", regression_gate.RATE_TOL)
    assert regression_gate.direction_and_tol(
        "mesh_d8_tokens_per_device_per_s") \
        == ("down", regression_gate.RATE_TOL)
    history = [{"mesh_d8_tokens_per_s": 100.0}] * 5
    regs, _ = regression_gate.compare(
        {"mesh_d8_tokens_per_s":
         100.0 * (1 - regression_gate.RATE_TOL) * 0.9}, history)
    assert [r["metric"] for r in regs] == ["mesh_d8_tokens_per_s"]


def test_eager_gap_direction_policy():
    """PR 10 satellite: the eager-gap trajectory is gate-pinned — the
    ratio regresses UP (explicit rule: the generic suffixes would not
    catch it), the ops/s throughput regresses DOWN."""
    assert regression_gate.direction_and_tol("eager_over_jit_ratio") \
        == ("up", regression_gate.RATE_TOL)
    assert regression_gate.direction_and_tol(
        "eager_elementwise_ops_per_s")[0] == "down"
    assert regression_gate.direction_and_tol(
        "eager_tiny_gpt_step_ms")[0] == "up"
    history = [{"eager_over_jit_ratio": 2.0,
                "eager_elementwise_ops_per_s": 4000.0}] * 5
    regs, checked = regression_gate.compare(
        {"eager_over_jit_ratio": 2.0 * (1 + regression_gate.RATE_TOL)
         * 1.5,
         "eager_elementwise_ops_per_s": 4000.0
         * (1 - regression_gate.RATE_TOL) / 2}, history)
    assert {r["metric"] for r in regs} == {
        "eager_over_jit_ratio", "eager_elementwise_ops_per_s"}
    gap = next(r for r in regs if r["metric"] == "eager_over_jit_ratio")
    assert gap["direction"] == "up"
    regs2, _ = regression_gate.compare(
        {"eager_over_jit_ratio": 1.8,
         "eager_elementwise_ops_per_s": 4100.0}, history)
    assert not regs2  # an IMPROVED gap never trips the gate


def test_compare_flags_both_directions():
    history = [{"step_ms": 100.0 + i, "tokens_per_s": 1000.0}
               for i in range(5)]
    regs, checked = regression_gate.compare(
        {"step_ms": 100.0 * (1 + regression_gate.TIME_TOL) * 3,
         "tokens_per_s": 1000.0 * (1 - regression_gate.RATE_TOL) / 2},
        history)
    assert {r["metric"] for r in regs} == {"step_ms", "tokens_per_s"}
    up = next(r for r in regs if r["metric"] == "step_ms")
    assert up["median"] == 102.0 and up["direction"] == "up"
    # within tolerance: clean
    regs2, checked2 = regression_gate.compare(
        {"step_ms": 103.0, "tokens_per_s": 990.0}, history)
    assert not regs2 and set(checked2) == {"step_ms", "tokens_per_s"}


def test_compare_needs_min_history():
    history = [{"step_ms": 100.0}] * (regression_gate.MIN_HISTORY - 1)
    regs, checked = regression_gate.compare({"step_ms": 1e9}, history)
    assert not regs and not checked  # too little history: record only


def test_compare_ignores_unknown_and_nonnumeric():
    history = [{"step_ms": 100.0}] * 5
    regs, checked = regression_gate.compare(
        {"step_ms": 101.0, "suite_targets": 9, "note": "hi"}, history)
    assert checked == ["step_ms"] and not regs


def test_self_test_detects_synthetic_regression():
    # the acceptance pin: the gate FAILS on an injected regression and
    # PASSES clean — self_test() exits 0 only when both hold
    assert regression_gate.self_test() == 0


def test_record_suite_appends_and_advises(ledger, monkeypatch):
    monkeypatch.setattr(bench_ledger, "DEFAULT_PATH", ledger)
    for _ in range(4):
        regression_gate.record_suite(10.0, 3, path=ledger)
    assert len(bench_ledger.entries(ledger, kind="suite_gate")) == 4
    # comparable (same target count) timing regression -> advisory rows
    regs = regression_gate.record_suite(100.0, 3, path=ledger)
    assert any(r["metric"] == "suite_wall_s" for r in regs)
    # different target set: no comparable history, no advisory
    regs = regression_gate.record_suite(100.0, 12, path=ledger)
    assert regs == []
    assert len(bench_ledger.entries(ledger, kind="suite_gate")) == 6
