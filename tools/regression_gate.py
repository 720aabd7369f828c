"""Regression gate over the continuous-bench ledger.

Compares the CURRENT run's measurements against the **median of the
last N same-kind ledger entries** (tools/bench_ledger.py) with
per-metric tolerances, then appends the current run — so the ledger is
self-extending and the baseline is a rolling median (robust to one
noisy CI run; a genuine regression shifts every subsequent comparison
until fixed or acknowledged).

Direction is per metric: time-like metrics (``*_us``/``*_ms``/``*_s``)
regress UPWARD, throughput-like metrics (``*tokens_per_s``, ``*_rate``,
``*mfu``) regress DOWNWARD. Tolerances are generous for wall-clock
measurements on a shared CI box (default 75%) and tight for
``headline_*`` numbers (5%) — override per-run via
``REG_GATE_TIME_TOL`` / ``REG_GATE_RATE_TOL``.

Modes::

    python tools/regression_gate.py              # measure + compare + append
    python tools/regression_gate.py --self-test  # synthetic-regression check
    python tools/regression_gate.py --record-suite 12.3 --targets 4
                                                 # suite_gate timing entry

``--self-test`` proves the detector end-to-end against a synthetic
ledger in a temp dir: a fabricated 10x step-time regression MUST fail
and an in-tolerance run MUST pass — exit 0 means the detector works
(this is what tools/suite_gate.py runs pre-commit; the full measure
mode runs from tools/accounting_gate.py and by hand).

Fewer than ``MIN_HISTORY`` prior entries = nothing to regress against:
the run appends and passes (priming the ledger).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import bench_ledger  # noqa: E402

N_HISTORY = int(os.environ.get("REG_GATE_HISTORY", "8"))
MIN_HISTORY = int(os.environ.get("REG_GATE_MIN_HISTORY", "3"))
TIME_TOL = float(os.environ.get("REG_GATE_TIME_TOL", "0.75"))
RATE_TOL = float(os.environ.get("REG_GATE_RATE_TOL", "0.25"))
HEADLINE_TOL = float(os.environ.get("REG_GATE_HEADLINE_TOL", "0.05"))


def direction_and_tol(name):
    """('up'|'down', rel_tol) — 'up' means larger-is-worse — or None
    for metrics the gate only records (counts, config echoes)."""
    if name == "serve_done":
        # success sentinel (1.0 iff the probe request reached DONE):
        # ANY drop below the all-1.0 median is a failure, zero tolerance
        return ("down", 0.0)
    if name == "eager_over_jit_ratio":
        # the eager-gap headline (bench.py _eager_vs_jit_budget, kind
        # "eager_gap"): a RATIO where larger is worse — the generic
        # suffix rules would misread it, so it gets an explicit policy
        return ("up", RATE_TOL)
    if name.startswith("headline_"):
        return ("down", HEADLINE_TOL) if "tokens_per_s" in name \
            or "mfu" in name else ("up", HEADLINE_TOL)
    if "goodput" in name or "hit_rate" in name:
        # quality floors (kind fleet_load / overload_gate): fractions in
        # [0, 1] where a DROP is the regression — no time/rate suffix to
        # key off (e.g. high_goodput_frac), so match by substring
        return ("down", RATE_TOL)
    if name.endswith("_ok"):
        # pass/fail sentinels (scenario_ok, gate_ok — kind fleet_load):
        # any drop below an all-1.0 median is a failure, zero tolerance
        return ("down", 0.0)
    if name in ("quant_decode_pallas_over_dense",
                "quant_matmul_pallas_over_xla"):
        # kernel-tier ratios (kind quant_kernels): Pallas step time
        # over its dense/XLA reference. HONEST CPU caveat: tier-1 runs
        # the kernels in interpret mode, so the ratio is an overhead
        # proxy (interpret >> XLA), not the TPU speedup — the gate only
        # guards against the kernel path getting structurally slower
        return ("up", TIME_TOL)
    if "dup_frames" in name:
        # re-shipped frames after ambiguous rpc timeouts (kind disagg):
        # each one is safe (import dedups, admission is idempotent) but
        # GROWTH means the channel is flaking more — larger is worse
        return ("up", RATE_TOL)
    if name == "full_prefill_ratio":
        # the fleet-cache headline (kind fleet_cache): aware-over-blind
        # full-prefill tokens, ~1/N when cross-replica pulls land —
        # a RATIO where larger is worse, like eager_over_jit_ratio
        return ("up", RATE_TOL)
    if "pull_fallbacks" in name or "fallbacks" in name:
        # fleet-cache peer-pull fallbacks (kind fleet_cache) and disagg
        # fallbacks (kind fleet_load): every one is a request that
        # degraded to local/co-located serving — correct but slower, so
        # GROWTH means the fabric or the advertisements got less honest
        return ("up", RATE_TOL)
    if "peer_pulls" in name or "coverage_hits" in name:
        # fleet-cache plane effectiveness (kind fleet_cache /
        # fleet_load): a DROP means the digest routing stopped finding
        # (or stopped using) cross-replica prefixes — the plane quietly
        # reverting to cache-blind without failing its gate
        return ("down", RATE_TOL)
    if "lease_expired" in name:
        # remote-handoff leases that ran out before a terminal status
        # (kind disagg): every one is a presumed-dead peer and a
        # cursor-replayed reclaim — a healthy fleet renews faster than
        # it expires, so GROWTH is the regression
        return ("up", RATE_TOL)
    if "transfer_bytes" in name:
        # disaggregated handoff payload size (kind disagg): GROWTH is
        # the regression — a fatter frame per handoff means scale rows
        # duplicated or dead weight riding the fabric
        return ("up", RATE_TOL)
    if "handoff" in name:
        # disaggregated handoff count (kind disagg): a DROP means
        # requests silently degraded to co-located fallback — the
        # fabric stopped doing its job without failing the gate
        return ("down", RATE_TOL)
    # throughput suffixes FIRST: "tokens_per_s" also ends with "_s"
    # (_per_step: the speculative decode multiple; _mult: the int8 KV
    # capacity multiplier — both larger-is-better, kind spec_gate /
    # decode_tiers)
    if name.endswith(("_per_s", "_rate", "_mfu",
                      "_per_step", "_mult")) or name == "mfu":
        return ("down", RATE_TOL)
    if name.endswith(("_us", "_ms", "_s", "_seconds", "_ns")):
        return ("up", TIME_TOL)
    return None


def compare(current, history, min_history=MIN_HISTORY):
    """Compare ``current`` (flat metrics dict) against the per-metric
    median of ``history`` (list of metrics dicts). Returns
    (regressions, checked): each regression names the metric, its
    value, the median baseline, and the tripped limit."""
    regressions, checked = [], []
    for name, value in sorted(current.items()):
        if not isinstance(value, (int, float)):
            continue
        dt = direction_and_tol(name)
        if dt is None:
            continue
        direction, tol = dt
        past = [h[name] for h in history
                if isinstance(h.get(name), (int, float))]
        if len(past) < min_history:
            continue
        med = statistics.median(past)
        if direction == "up":
            limit = med * (1.0 + tol)
            # med <= 0 is a degenerate baseline (no meaningful limit)
            bad = med > 0 and value > limit
        else:
            limit = med * (1.0 - tol)
            bad = value < limit
        checked.append(name)
        if bad:
            regressions.append({"metric": name, "current": value,
                                "median": med, "limit": limit,
                                "direction": direction, "n": len(past)})
    return regressions, checked


def measure():
    """The quick fixed corpus: a tiny-Llama serving run's warm TTFT and
    mean step time, and the disarmed-accounting overhead."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import Llama, LlamaConfig
    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    model = Llama(LlamaConfig.tiny())
    model.eval()
    rng = np.random.default_rng(0)
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, bucket_cap=32, background=False)
    # warm every bucket + the decode program
    for n in (5, 9, 17):
        eng.submit(rng.integers(0, 255, (n,)).astype("int64"),
                   max_new_tokens=4)
        eng.run_until_idle()
    before = metrics.snapshot("serving.")
    t0 = time.perf_counter()
    h = eng.submit(rng.integers(0, 255, (6,)).astype("int64"),
                   max_new_tokens=8)
    eng.step()
    ttft_ms = (time.perf_counter() - t0) * 1000.0
    eng.run_until_idle()
    after = metrics.snapshot("serving.")
    steps = after["serving.step_us"]["count"] - \
        before["serving.step_us"]["count"]
    mean_step_ms = (after["serving.step_us"]["sum"]
                    - before["serving.step_us"]["sum"]) \
        / max(steps, 1) / 1000.0
    eng.close()
    m = {"serve_warm_ttft_ms": round(ttft_ms, 3),
         "serve_mean_step_ms": round(mean_step_ms, 3),
         "serve_done": 1.0 if h.status == "DONE" else 0.0}
    from accounting_gate import measure_disarmed_us
    m["accounting_disarmed_us"] = round(measure_disarmed_us(), 4)
    return m


def run(path=None, kind="regression_gate"):
    current = measure()
    history = [e["metrics"] for e in
               bench_ledger.last(N_HISTORY, kind, path)]
    regressions, checked = compare(current, history)
    bench_ledger.append_entry(kind, current, path=path)
    for name in sorted(current):
        print(f"[regression-gate]   {name} = {current[name]}")
    if len(history) < MIN_HISTORY:
        print(f"[regression-gate] priming: {len(history)} prior "
              f"entries (< {MIN_HISTORY}); appended, PASS")
        return 0
    if regressions:
        for r in regressions:
            print(f"[regression-gate] REGRESSION {r['metric']}: "
                  f"{r['current']:.4g} vs median {r['median']:.4g} "
                  f"over {r['n']} runs (limit {r['limit']:.4g})")
        print("[regression-gate] FAIL")
        return 1
    print(f"[regression-gate] {len(checked)} metric(s) within "
          f"tolerance of the {len(history)}-run median; appended. PASS")
    return 0


def record_suite(wall_s, targets, path=None):
    """suite_gate hook: append the suite timing and ADVISE (never
    block — the target set varies per diff, so timing medians are only
    a smell) when the wall time regressed past tolerance."""
    current = {"suite_wall_s": round(float(wall_s), 3),
               "suite_targets": int(targets)}
    history = [e["metrics"] for e in
               bench_ledger.last(N_HISTORY, "suite_gate", path)]
    bench_ledger.append_entry("suite_gate", current, path=path)
    same_size = [h for h in history
                 if h.get("suite_targets") == int(targets)]
    regs, _ = compare(current, same_size)
    for r in regs:
        print(f"[regression-gate] ADVISORY suite timing: {r['metric']} "
              f"{r['current']:.1f} vs median {r['median']:.1f} "
              f"({r['n']} comparable runs)")
    return regs


def self_test():
    """Prove the detector on a synthetic ledger: a 10x step-time /
    halved-throughput run MUST be flagged, an in-tolerance run MUST
    pass. Exit 0 iff both hold."""
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "ledger.jsonl")
        base = {"serve_mean_step_ms": 100.0, "headline_tokens_per_s":
                37826.5, "accounting_disarmed_us": 2.0}
        for i in range(5):
            bench_ledger.append_entry(
                "self_test", {**base,
                              "serve_mean_step_ms": 100.0 + i},
                path=path)
        history = [e["metrics"] for e in
                   bench_ledger.last(8, "self_test", path)]
        bad = {"serve_mean_step_ms": 1000.0,        # 10x time regression
               "headline_tokens_per_s": 18000.0,    # halved headline
               "accounting_disarmed_us": 2.1}
        regs, _ = compare(bad, history)
        flagged = {r["metric"] for r in regs}
        want = {"serve_mean_step_ms", "headline_tokens_per_s"}
        ok_detect = flagged == want
        good = {**base, "serve_mean_step_ms": 110.0}
        regs2, checked2 = compare(good, history)
        ok_clean = not regs2 and len(checked2) >= 3
        # the ledger file itself: append-only, malformed-line tolerant
        with open(path, "a") as f:
            f.write("{corrupt\n")
        ok_ledger = len(bench_ledger.entries(path)) == 5
        ok = ok_detect and ok_clean and ok_ledger
        print(f"[regression-gate] self-test: injected regression "
              f"flagged={sorted(flagged)} (want {sorted(want)}), "
              f"clean run regressions={len(regs2)} "
              f"(checked {len(checked2)}), corrupt-line skipped="
              f"{ok_ledger} {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1


def main(argv):
    if "--self-test" in argv:
        return self_test()
    if "--record-suite" in argv:
        i = argv.index("--record-suite")
        wall = float(argv[i + 1])
        targets = 0
        if "--targets" in argv:
            targets = int(argv[argv.index("--targets") + 1])
        record_suite(wall, targets)
        return 0
    return run()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
