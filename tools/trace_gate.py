"""Tracing-layer gate: overhead budgets + the end-to-end trace contract.

Tracing is ALWAYS compiled in (sampling decides what records), so this
gate pins what the observability PR promised, in order of importance:

  1. overhead   — the disarmed path (`FLAGS_trace_enable=0`) stays a
     near-free global read under ``TRACE_GATE_BUDGET_US``; at the
     default sample rate a full record-into-ring span stays under
     ``TRACE_GATE_SPAN_BUDGET_US`` (generous: catches a lock convoy or
     an allocation storm, not scheduler jitter); an engine-thread
     ``tracing.phase`` with no profiler session (two clock reads, one
     histogram observe, one TraceMe) stays under 5 us, and a served
     request feeds every phase histogram of the step;
  2. completeness — one served request produces a complete exportable
     trace: submit root, queue-wait, prefill, one decode slice per
     decoded token, terminal event, all parent-linked;
  3. exemplars  — the serving SLO histograms (`ttft_us`, `itl_us`)
     carry exemplars naming trace_ids the ring can still export;
  4. scrape     — `/metrics` round-trips through a real HTTP GET and
     `export.parse_prometheus`, values matching `metrics.snapshot()`.

Budgets are env-overridable (TRACE_GATE_*). Exit 0 on pass, 1 on fail;
one line per check. Runs under JAX_PLATFORMS=cpu (tier-1); wired into
tools/suite_gate.py beside the metrics/serving gates.
"""

import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BUDGET_US = float(os.environ.get("TRACE_GATE_BUDGET_US", "5"))
SPAN_BUDGET_US = float(os.environ.get("TRACE_GATE_SPAN_BUDGET_US", "75"))
# a phase is always on: ~17 a scheduler step, 3 more a prefill
PHASE_BUDGET_US = 5.0


def _med_us(fn, n, trials=5):
    outs = []
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        outs.append((time.perf_counter() - t0) * 1e6 / n)
    return statistics.median(outs)


def check_overhead():
    import paddle_tpu as paddle
    from paddle_tpu.profiler import tracing

    saved = paddle.get_flags(["FLAGS_trace_enable", "FLAGS_trace_sample"])
    try:
        paddle.set_flags({"FLAGS_trace_enable": False})
        off_us = _med_us(lambda: tracing.span("gate.off"), 20_000)
        paddle.set_flags({"FLAGS_trace_enable": True,
                          "FLAGS_trace_sample": 1.0})

        def one_span():
            with tracing.span("gate.on", parent=root):
                pass

        root = tracing.start_trace("gate.root")
        on_us = _med_us(one_span, 5_000)
        root.end()
    finally:
        paddle.set_flags(saved)
    ok = off_us < BUDGET_US and on_us < SPAN_BUDGET_US
    print(f"[trace-gate] overhead: disarmed={off_us:.3f}us "
          f"(budget {BUDGET_US}us) sampled span={on_us:.2f}us "
          f"(budget {SPAN_BUDGET_US}us) {'PASS' if ok else 'FAIL'}")
    return ok


def check_phases(before):
    """The always-on phase span: its cost with no profiler session, and
    that the request ``_serve_one`` served fed every phase histogram of
    a foreground engine's step (no driver thread, so no ``no_work``)."""
    from paddle_tpu.profiler import metrics, tracing

    def one_phase():
        with tracing.phase("serving.decode.dispatch", batch=4,
                           context_tokens=99):
            pass

    after = metrics.snapshot("serving.phase.")
    us = _med_us(one_phase, 20_000)
    silent = [n for n in tracing.PHASE_NAMES
              if n not in ("serving.step", "serving.engine.no_work")
              and after[tracing.phase_histogram_name(n)]["count"]
              <= before[tracing.phase_histogram_name(n)]["count"]]
    ok = us < PHASE_BUDGET_US and not silent
    print(f"[trace-gate] phases: no-session phase={us:.3f}us "
          f"(budget {PHASE_BUDGET_US}us) silent histograms={silent} "
          f"{'PASS' if ok else 'FAIL'}")
    return ok


def _serve_one():
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import Llama, LlamaConfig
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    model = Llama(LlamaConfig.tiny())
    model.eval()
    rng = np.random.default_rng(0)
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False)
    handle = eng.submit(rng.integers(0, 255, (6,)).astype("int64"),
                        max_new_tokens=5)
    eng.run_until_idle()
    return eng, handle


def check_complete_trace(handle):
    from paddle_tpu.profiler import tracing

    tr = tracing.get_trace(handle.trace_id) if handle.trace_id else []
    names = [r["name"] for r in tr]
    ids = {r["span"] for r in tr}
    linked = all(r["parent"] is None or r["parent"] in ids for r in tr)
    want = {"serving.request": 1, "serving.queue_wait": 1,
            "serving.prefill": 1, "serving.decode_step": 4,
            "serving.terminal": 1}
    counts = {n: names.count(n) for n in want}
    ok = handle.status == "DONE" and counts == want and linked \
        and bool(tracing.export_trace(handle.trace_id)["traceEvents"])
    print(f"[trace-gate] completeness: spans={counts} "
          f"parent-linked={linked} {'PASS' if ok else 'FAIL'}")
    return ok


def check_exemplars():
    from paddle_tpu.profiler import metrics, tracing

    snap = metrics.snapshot("serving.")
    ok = True
    for name in ("serving.ttft_us", "serving.itl_us"):
        exs = (snap.get(name) or {}).get("exemplars") or {}
        resolvable = [ex for ex in exs.values()
                      if ex["trace_id"] and tracing.get_trace(
                          ex["trace_id"])]
        ok = ok and bool(resolvable)
        print(f"[trace-gate] exemplars: {name} buckets={len(exs)} "
              f"resolvable={len(resolvable)} "
              f"{'PASS' if resolvable else 'FAIL'}")
    return ok


def check_scrape(eng):
    import json
    import urllib.request

    from paddle_tpu.profiler import export, metrics

    srv = eng.serve_metrics()
    body = urllib.request.urlopen(srv.url("/metrics"),
                                  timeout=10).read().decode()
    parsed = export.parse_prometheus(body)
    snap = metrics.snapshot("serving.")
    match = (parsed["serving_completed"]["value"]
             == snap["serving.completed"]
             and parsed["serving_ttft_us"]["count"]
             == snap["serving.ttft_us"]["count"])
    hz = json.loads(urllib.request.urlopen(srv.url("/healthz"),
                                           timeout=10).read())
    ok = body.rstrip().endswith("# EOF") and match \
        and hz["status"] == "ok"
    print(f"[trace-gate] scrape: {len(parsed)} metrics parsed, "
          f"values match={match} healthz={hz['status']} "
          f"{'PASS' if ok else 'FAIL'}")
    return ok


def main():
    from paddle_tpu.profiler import metrics

    ok1 = check_overhead()
    phases_before = metrics.snapshot("serving.phase.")
    eng, handle = _serve_one()
    try:
        ok2 = check_complete_trace(handle)
        ok3 = check_exemplars()
        ok4 = check_scrape(eng)
        ok5 = check_phases(phases_before)
    finally:
        eng.close()
    if ok1 and ok2 and ok3 and ok4 and ok5:
        print("[trace-gate] PASS")
        return 0
    print("[trace-gate] FAIL")
    return 1


if __name__ == "__main__":
    sys.exit(main())
