"""Continuous-bench regression ledger: append-only JSONL of gate/bench
measurements.

A point-in-time snapshot that nothing reads across runs lets a PR that
quietly shaved 10% off a gate's number sail through review. The ledger
fixes that: each gate/bench run appends ONE line (wall-clock ts, git SHA, a kind tag,
and a flat metrics dict) to ``BENCH_LEDGER.jsonl``, and
``tools/regression_gate.py`` compares the current run against the
median of the last N same-kind entries with per-metric tolerances.

Append-only by design: entries are never rewritten, a malformed line is
skipped on read (a crashed writer must not poison history), and two
processes appending concurrently each land a complete line (single
``write`` of one line under O_APPEND semantics).

Known kinds (each writer documents its metrics): ``regression_gate``
(tools/regression_gate.py measure mode), ``suite_gate`` (pre-commit
wall time, advisory), ``eager_gap`` (bench.py eager-vs-jit rung),
``fusion_gate`` (tools/fusion_gate.py async A/B), ``fleet_gate``
(tools/fleet_gate.py aggregator refresh + federation checks),
``router_gate`` (tools/router_gate.py zero-cold-start: cold vs warm
process compile seconds, AOT hit counts, traffic-shift/failover
bits), ``overload_gate`` (tools/overload_gate.py: high-priority
goodput fraction under ~8x oversubscription, shed/reject counts,
breaker + flags-off check bits), ``spec_gate`` (tools/spec_gate.py
decode speed tiers: speculative tokens/step multiple, draft
acceptance rate, int8 KV capacity multiplier, equivalence bits),
``decode_tiers`` (bench.py decode rung: base vs speculative vs
quantized tokens/s on the serving scheduler), ``fleet_load``
(tools/fleet_load_gate.py scenario observatory: per-scenario rollup of
the worst phase — scenario_ok/gate_ok pass bits, arrivals/accepted/
shed/failover/dropped counts, min high_goodput_frac, min
prefix_hit_rate, max ttft_p95_us — every number read through
scenario-scoped profiler.metrics Windows, never a registry reset),
``disagg`` (tools/disagg_gate.py disaggregated serving: handoff and
fallback counts, transfer bytes/us, bit-equivalence / zero-reprefill
/ fail-open / disarmed check bits), ``kernel_gate``
(tools/kernel_gate.py Pallas serving-kernel tier: equivalence /
counter-routing / warmup-zero-recompile / forced-off check bits),
``quant_kernels`` (bench.py quantized-kernel rung: dense vs Pallas
int8 decode attention and XLA vs Pallas int8 matmul step times plus
their ratios — CPU interpret-mode proxies, see the rung's note),
``fleet_cache`` (tools/fleet_cache_gate.py fleet cache plane:
blind-vs-aware full-prefill token A/B and its ~1/N ratio, peer-pull
and fallback counts, autoscale edge counts, zero-reprefill /
fail-open / flags-off check bits).
The ledger itself is schema-free — any kind/metrics pair appends.

CLI::

    python tools/bench_ledger.py --show 10                # recent entries
    python tools/bench_ledger.py --kind mybench \
        --metrics '{"tokens_per_s": 37826.5}'             # append one
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
DEFAULT_PATH = os.path.join(REPO, "BENCH_LEDGER.jsonl")

__all__ = ["append_entry", "entries", "last", "git_sha",
           "DEFAULT_PATH"]


def git_sha(repo=REPO):
    """Short HEAD sha, or 'unknown' outside a repo / without git."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=repo,
            capture_output=True, text=True, timeout=10)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except Exception:  # noqa: BLE001 — ledger must work without git
        return "unknown"


def append_entry(kind, metrics, *, path=None, meta=None):
    """Append one ledger line; returns the entry dict. ``metrics`` must
    be a flat {name: number} dict (that is what the regression gate can
    take medians over); non-numeric values are kept but ignored by
    comparisons."""
    entry = {"ts": time.time(),
             "iso": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
             "git_sha": git_sha(),
             "kind": str(kind),
             "metrics": dict(metrics)}
    if meta:
        entry["meta"] = dict(meta)
    line = json.dumps(entry, sort_keys=True)
    with open(path or DEFAULT_PATH, "a") as f:
        f.write(line + "\n")
    return entry


def entries(path=None, kind=None):
    """Every parseable entry, oldest first (malformed lines skipped —
    the ledger outlives crashed writers)."""
    path = path or DEFAULT_PATH
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                e = json.loads(line)
            except ValueError:
                continue
            if not isinstance(e, dict) or "metrics" not in e:
                continue
            if kind is not None and e.get("kind") != kind:
                continue
            out.append(e)
    return out


def last(n=8, kind=None, path=None):
    """The most recent ``n`` entries (oldest of them first)."""
    return entries(path, kind)[-n:]


def main(argv):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--kind")
    ap.add_argument("--metrics", help="flat JSON dict to append")
    ap.add_argument("--path", default=None)
    ap.add_argument("--show", nargs="?", const=10, type=int,
                    default=None, help="print the last N entries")
    args = ap.parse_args(argv)
    if args.show is not None:
        for e in last(args.show, args.kind, args.path):
            print(json.dumps(e, sort_keys=True))
        return 0
    if args.kind and args.metrics:
        e = append_entry(args.kind, json.loads(args.metrics),
                         path=args.path)
        print(f"bench-ledger: appended {e['kind']}@{e['git_sha']} "
              f"({len(e['metrics'])} metrics)")
        return 0
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
