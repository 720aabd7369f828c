"""Driver of the serving cells: a ``ServingEngine`` over a Llama-shaped
configuration, loaded by ``client.Client`` with the cell's traffic mix.

Timeline of a run (all on ``time.perf_counter``):

    process start .. build, warmup(), warm-up traffic .. generator starts
    .. lead-in .. [window: --seconds] .. generator stops .. drain ..
    correctness (statuses, counters, plain reference) .. result

``setup_s`` is process start to the start of the window. Inside the
window the main thread only sleeps; the client thread submits, the
engine's thread steps and stamps tokens through ``on_token``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import client as client_mod
from benchmarks import harness, traffic
from benchmarks.build import build_model
from benchmarks.reference import rope_gqa_swiglu as reference

# tokens a warm-up or a correctness request generates
_SHORT_OUTPUT = 16
_CHECK_REQUESTS = 4


def _llama_config(fields):
    from paddle_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=fields["vocab_size"], hidden_size=fields["hidden_size"],
        intermediate_size=fields["intermediate_size"],
        num_layers=fields["num_hidden_layers"],
        num_heads=fields["num_attention_heads"],
        num_kv_heads=fields["num_key_value_heads"],
        max_position_embeddings=fields["max_position_embeddings"],
        rms_norm_eps=fields["rms_norm_eps"],
        rope_theta=fields["rope_theta"],
        initializer_range=fields["initializer_range"],
        tie_word_embeddings=fields["tie_word_embeddings"])


def _warm_traffic(engine, mix, slots, rng):
    """One request for every prefill bucket (power of two) the mix's
    prompt lengths reach, and enough of them at once to run a full decode
    batch: the programs the served path compiles on first use, which
    ``warmup()`` does not reach (PERF.md, PR 21)."""
    lo = int(round(traffic.quantile(mix.p["prompt_len"], 0.0)))
    hi = int(round(traffic.quantile(mix.p["prompt_len"], 1.0)))
    lengths, n = [], 1
    while n < lo:
        n <<= 1
    while True:
        lengths.append(min(n, hi))
        if n >= hi:
            break
        n <<= 1
    lengths += [lo] * max(slots - len(lengths), 0)
    handles = [engine.submit(rng.integers(3, mix.vocab, size=n),
                             max_new_tokens=_SHORT_OUTPUT)
               for n in lengths]
    for h in handles:
        h.result(timeout=1200)


def _reference_check(engine, model, fields, seed, max_seq_len):
    """Serve a few seeded prompts (64 to 512 tokens where the context
    allows) and hold their tokens to the plain reference's logits
    (``reference.margin_check``)."""
    rng = np.random.default_rng([int(seed), 99])
    hi = min(512, max_seq_len // 4)
    lo = min(64, hi // 2)
    pad_to = hi + _SHORT_OUTPUT
    vocab = int(fields["vocab_size"])
    weights = reference.weights_of(model)
    ref_fields = {"num_heads": fields["num_attention_heads"],
                  "num_kv_heads": fields["num_key_value_heads"],
                  "rope_theta": fields["rope_theta"],
                  "rms_norm_eps": fields["rms_norm_eps"]}
    prompts = [rng.integers(3, vocab, size=int(n))
               for n in rng.integers(lo, hi + 1, size=_CHECK_REQUESTS)]
    handles = [engine.submit(p, max_new_tokens=_SHORT_OUTPUT)
               for p in prompts]
    worst = 0.0
    for prompt, h in zip(prompts, handles):
        toks = [int(t) for t in h.result(timeout=600)]
        if str(h.status) != "DONE" or len(toks) != _SHORT_OUTPUT:
            return {"ok": False, "why": f"check request ended {h.status} "
                                        f"with {len(toks)} tokens"}
        # one padded length for every prompt and seed: causal, so the
        # padding changes nothing before it, and the reference compiles
        # once
        ids = np.zeros((pad_to,), np.int64)
        ids[:len(prompt)] = prompt
        ids[len(prompt):len(prompt) + len(toks)] = toks
        share, _scale = reference.margin_check(
            reference.logits(weights, ref_fields, ids), len(prompt), toks)
        worst = max(worst, share)
    return {"ok": worst <= reference.MARGIN, "worst_deficit_share": worst,
            "margin": reference.MARGIN}


def _client_stats(records, t0, t1, open_loop):
    """Latencies of the judged requests (due inside the window) and the
    tokens delivered inside it."""
    judged = [r for r in records if t0 <= r.due < t1]
    ok = [r for r in judged if r.complete]
    ttft = [(r.times[0] - r.due) * 1e3 for r in ok]
    itl = [g * 1e3 for r in ok for g in np.diff(r.times)]
    late = [(r.sent - r.due) * 1e3 for r in judged]
    tokens = sum(1 for r in records for t in r.times if t0 <= t < t1)
    completed = sum(1 for r in records
                    if r.complete and t0 <= r.times[-1] < t1)
    return {"judged": len(judged), "judged_ok": len(ok),
            "tokens_in_window": tokens, "completed_in_window": completed,
            "window_s": t1 - t0, "ttft_ms": ttft, "itl_ms": itl,
            "late_ms": late if open_loop else []}


def run(run):
    import jax.numpy as jnp

    from paddle_tpu.models import Llama
    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import ServingEngine

    cell = run.cell
    fields = cell.config
    dtype = fields["torch_dtype"]
    shape = cell.workload["engine"]
    mix = traffic.RequestMix(cell.traffic, run.seed, fields["vocab_size"],
                             seconds=run.seconds)
    open_loop = cell.traffic["loop"] == "open"
    lead_in = float(cell.traffic["lead_in_s"])

    marks = {"to_driver_s": harness.process_age_s()}
    t_mark = time.perf_counter()

    def mark(name):
        nonlocal t_mark
        now = time.perf_counter()
        marks[name], t_mark = now - t_mark, now

    # the route counters move when the decode program is traced
    kernels_before = metrics.snapshot("serving.kernel.")
    degrade_before = metrics.snapshot("resilience.degrade.")
    model = build_model(Llama, _llama_config(fields), dtype, run.seed)
    model.eval()
    mark("build_s")
    engine = ServingEngine(
        model, temperature=0.0, ready=False, dtype=jnp.dtype(dtype),
        max_batch=shape["slots"], block_size=shape["block_size"],
        max_seq_len=shape["max_seq_len"], bucket_cap=shape["bucket_cap"],
        paged_kernel=shape.get("paged_kernel"))
    try:
        engine.warmup()
        mark("engine_and_warmup_s")
        _warm_traffic(engine, mix, shape["slots"],
                      np.random.default_rng([run.seed, 98]))
        mark("warm_traffic_s")
        tracer = run.trace_slice()
        cache = engine.cache

        def kv_active_share():
            occ = cache.occupancy()
            return occ["active"] / occ["usable"]

        load = client_mod.Client(
            engine, mix, sample=kv_active_share,
            annotate=tracer.annotate if tracer else None)
        load.start(horizon_s=lead_in + run.seconds)
        t0 = load.started_at + lead_in
        t1 = t0 + run.seconds
        if tracer:
            # a slice in the middle of the window, a fifth of it but no
            # more than 3 s: a trace of serving is ~12 MB a second
            tracer.schedule(t0 + 0.4 * run.seconds,
                            min(3.0, 0.2 * run.seconds))
        time.sleep(max(t0 - time.perf_counter(), 0.0))
        setup_s = harness.process_age_s()
        before = metrics.snapshot()
        time.sleep(max(t1 - time.perf_counter(), 0.0))
        after = metrics.snapshot()
        load.stop()
        drained_s = load.wait(float(cell.traffic["drain_s"]))
        memory_peak = run.memory_peak_bytes()
        reduced = tracer.finish() if tracer else None

        # -- correct -----------------------------------------------------
        records = load.records
        counted = [r for r in records if t0 <= r.due < t1] if open_loop \
            else [r for r in records if not r.cancelled]
        vocab = int(fields["vocab_size"])
        failed = 0
        for r in counted:
            toks = r.handle.tokens() if r.handle is not None else []
            if not (r.complete and str(r.handle.status) == "DONE"
                    and len(toks) == r.n_new
                    and all(0 <= int(t) < vocab for t in toks)):
                failed += 1
        kernels = harness.registry_delta(
            kernels_before, metrics.snapshot("serving.kernel."))
        degraded = harness.registry_delta(
            degrade_before, metrics.snapshot("resilience.degrade."))
        # a rehearsal on the CPU runs the same kernel interpreted
        route_ok = (kernels.get("serving.kernel.pallas", 0) > 0
                    and kernels.get("serving.kernel.dense", 0) == 0
                    and (run.rehearsal
                         or kernels.get("serving.kernel.interpret", 0) == 0)
                    and not any(degraded.values()))
        ref = _reference_check(engine, model, fields, run.seed,
                               shape["max_seq_len"])
    finally:
        engine.close()

    stats = _client_stats(records, t0, t1, open_loop)
    window = [s for t, s in load.samples if t0 <= t < t1]
    delta = harness.registry_delta(before, after)
    end_to_end = {"setup_s": setup_s,
                  "serve_tok_s": stats["tokens_in_window"] / run.seconds}
    if stats["ttft_ms"]:
        end_to_end["ttft_p95_ms"] = harness.percentile(stats["ttft_ms"], 95)
        end_to_end["itl_p95_ms"] = harness.percentile(stats["itl_ms"], 95)
    notes = {
        "setup": dict(marks, lead_in_s=lead_in),
        "memory_stats": {k: v for k, v in
                         (run.devices[0].memory_stats() or {}).items()
                         if "bytes" in k},
        "requests": {"sent": len(records), "judged": stats["judged"],
                     "judged_ok": stats["judged_ok"],
                     "withdrawn_at_stop": sum(r.cancelled for r in records)},
        "completed_per_s": stats["completed_in_window"] / run.seconds,
        "tokens_in_window": stats["tokens_in_window"],
        "drained_s": drained_s, "kernel_route": kernels,
        "degraded": {k: v for k, v in degraded.items() if v},
        "reference": ref,
        "window_compiles": delta.get("xla.compile.count", 0)}
    if stats["ttft_ms"]:
        for key, limit in (("ttft_ms", 1000.0), ("itl_ms", 100.0)):
            v = stats[key]
            notes[key] = {"n": len(v), "p50": harness.percentile(v, 50),
                          "p95": harness.percentile(v, 95),
                          "share_within_limit":
                              sum(x <= limit for x in v) / len(v),
                          "limit_ms": limit}
    return {"correct": failed == 0 and route_ok and ref["ok"],
            "attempted": len(counted), "failed": failed,
            "end_to_end": end_to_end, "memory_peak_bytes": memory_peak,
            "notes": notes,
            "ctx": {"counters": delta, "client": stats, "trace": reduced,
                    "kv_active_share": window, "seconds": run.seconds}}
