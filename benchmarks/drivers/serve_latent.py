"""Driver of the latent-attention serving cells: a ``ServingEngine`` over
an ``xing4_0`` configuration (``paddle_tpu.models.Xing``: latent
attention over a cache of one row a token a layer, sigmoid-routed
experts beside a shared one, residual streams mixed by
hyper-connections), loaded by ``client.Client`` with the cell's traffic
mix. The timeline, the window, the client statistics and the warm-up
traffic are ``drivers/serve.py``'s, imported and not copied; what
differs is the model, the reference and what ``correct`` compares.

``correct``: every judged request DONE with its token count and ids in
the vocabulary; the absorbed decode attention and the grouped expert
matmuls on the Pallas route and nothing degraded; and, after the window,
at the timed load, the reference check: two more requests of the mix are
served through the same engine while every other slot runs other
prompts of the mix (``serve_under_load``), each generating
``check_output`` tokens (512 in the cell), so that their steps are the
window's: every slot live, a step ahead. Then, against
``reference/latent_moe_hc.py``:

(a) the served tokens against the plain reference's logits over prompt +
    answer: at every position the reference's maximum less its logit of
    the served token, as a share of the logits' scale; the 90th
    percentile over the answers' positions is held (``margin_p90``).
    This holds the prefill (expanded attention at a padded bucket) and
    then the decode steps through the latent cache (absorbed attention)
    to the full forward in the bulk. Not the largest: where the
    program's bfloat16 activations move a token's fourth and fifth
    expert scores past each other, the program and the reference run
    different experts and that position's logits differ by tenths of
    their scale with nothing wrong (the notes have the quantiles and the
    largest; single steps are held by (b)). And of ``tap_steps`` sampled
    decode steps of the first request, whose program reported them
    (``Xing.paged_decode_step``'s debug tap), the slot's logits against
    the full forward's at that position (``logit_rms``, the smallest
    over the steps: about one sampled step in four has a token that runs
    other experts here than there, and its whole logit vector then
    differs by tenths) and the rows the cache holds of the request against the
    rows the full forward would cache (``cached_rows``, the median over
    the positions, the worst layer): bfloat16 through every layer, so
    percents;
(b) the same steps sublayer by sublayer on what the program itself read
    (``reference.replay_step``): the stream maps from the streams
    (``mix``), what each sublayer was fed (``read``: a reading, under
    no limit), the row the step
    wrote (``row``), the attention's output over the cache's own rows
    (``attn``), the router's weights (``router``) and the feed-forward
    part's output under the program's own routing (``experts``). These
    hold what logits cannot hold to its precision: the program's
    bfloat16 activations move the logits by more than a row kept in 8
    bits, a bfloat16 router or five Sinkhorn rounds would.

``reference.LIMITS`` says which readings each limit lies between. The
rehearsal also reads the same records against the reference made to get
something wrong (``reference.FAULTS``): notes only, and not in a run on
the chip, where they took a traced run past its time (PERF.md, section
6, has the chip's readings of them).
"""

from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

from benchmarks import client as client_mod
from benchmarks import harness, traffic
from benchmarks.build import build_model
from benchmarks.reference import latent_moe_hc as reference

blockdiff = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                             "serve_blockdiff.py"))
serve = blockdiff.serve

_CHECK_REQUESTS = 2
# the full forward made to get these wrong, beside the replays
_FULL_FAULTS = ("no_shared", "other_head")

# the configuration file's keys that the model's dataclass spells otherwise
_RENAMED = {"num_hidden_layers": "num_layers",
            "num_attention_heads": "num_heads",
            "num_key_value_heads": "num_kv_heads"}


def xing_config(fields):
    """``XingConfig`` of a configuration file: every key the dataclass
    has, under its name there."""
    from paddle_tpu.models import XingConfig

    known = {f.name for f in dataclasses.fields(XingConfig)}
    named = {_RENAMED.get(k, k): v for k, v in fields.items()}
    return XingConfig(**{k: v for k, v in named.items() if k in known})


def weights_seed(workload, seed):
    """The key the weights are drawn from: the workload's ``weights_seed``
    where it names one, else the run's ``seed``. Random weights decide
    which experts the served tokens are routed to, and so how many
    experts' weights a decode step reads: a workload that fixes the key
    serves the same work on every seed, which then draws the prompts, the
    warm-up and the check's sample (PERF.md, section 4)."""
    return int(workload.get("weights_seed", seed))


class _Sampled(list):
    """The decode step's taps, every ``stride``-th of the first ``cap``
    x ``stride`` offered: the engine's thread appends, under the cache's
    lock."""

    def __init__(self, cap, stride):
        super().__init__()
        self.cap, self.stride, self.offered = cap, max(stride, 1), 0

    def append(self, item):
        if self.offered % self.stride == 0 and len(self) < self.cap:
            super().append(item)
        self.offered += 1

    @property
    def full(self):
        return len(self) >= self.cap


def _held_rows(cache, slot, upto, rope):
    """The rows ``slot`` holds of positions below ``upto``, a layer: (c
    [upto, latent], k_r [upto, rope]) float32. The caller holds
    ``pool_lock``: the engine's thread donates the pools every step."""
    import jax.numpy as jnp

    pages = -(-upto // cache.block_size)
    table = jnp.asarray(cache.block_tables[slot, :pages].copy())

    def rows(pool):
        got = np.array(pool[table], dtype=np.float32)
        return got.reshape(-1, got.shape[-1])[:upto]

    return [(rows(kp), rows(vp)[:, :rope])
            for kp, vp in zip(cache.k_pools, cache.v_pools)]


def serve_under_load(engine, mix, first, shape, n_new, tap_steps, rope):
    """Serve ``_CHECK_REQUESTS`` requests of the mix (its prompts
    ``first``... of the cycle), ``n_new`` tokens each, while the engine's
    other slots run other prompts of the mix: those are submitted first
    and the queue is served in order, so a check request is admitted to
    an engine whose other slots are all live, as in the window; they
    generate until they are withdrawn, when the check requests are done.
    The first check request's decode steps are tapped, ``tap_steps`` of
    them spread over the first three quarters of its answer, and once
    they are the rows its slot holds are read. Returns (prompts,
    handles, taps [(seq_len before the step, array)], the held rows a
    layer or None, the fewest slots live while they ran)."""
    cache, sched = engine.cache, engine.scheduler
    others = []
    for j in range(shape["slots"] - _CHECK_REQUESTS):
        prompt, _ = mix.request(first + j)
        others.append(engine.submit(
            prompt, max_new_tokens=shape["max_seq_len"] - len(prompt)))
    live = []
    taps = _Sampled(tap_steps, (3 * n_new // 4) // max(tap_steps, 1))
    held = None
    try:
        prompts = [mix.request(first + len(others) + j)[0]
                   for j in range(_CHECK_REQUESTS)]
        handles = [engine.submit(p, max_new_tokens=n_new) for p in prompts]
        watched = handles[0]._req  # noqa: SLF001 — the slot to tap
        while not all(h._req.done for h in handles):  # noqa: SLF001
            slot = watched.slot
            if slot >= 0 and sched.state_observer is None and not taps:
                sched.state_observer = (slot, taps)
            if taps.full and held is None and not watched.done:
                with cache.pool_lock:
                    if sched.running.get(slot) is watched:
                        held = _held_rows(cache, slot, taps[-1][0] + 1,
                                          rope)
                sched.state_observer = None
            if watched.slot >= 0:
                live.append(sum(cache._live))  # noqa: SLF001
            time.sleep(0.002)
    finally:
        sched.state_observer = None
        for h in others:
            h.cancel()
    return prompts, handles, list(taps), held, min(live) if live else 0


def reference_check(engine, model, fields, mix, shape, n_new, tap_steps,
                    with_faults):
    """The check of the module docstring. ``with_faults`` also reads
    every record against the planted faults: the notes say which limits
    each breaks."""
    first = mix.n * 1000 + int(np.random.default_rng(
        [mix.seed, 99]).integers(mix.n))
    rope = int(fields["qk_rope_head_dim"])
    prompts, handles, taps, held, live = serve_under_load(
        engine, mix, first, shape, n_new, tap_steps, rope)
    served = [[int(t) for t in h.tokens()] for h in handles]
    for h, toks in zip(handles, served):
        if str(h.status) != "DONE" or len(toks) != n_new or h.preempts:
            return {"ok": False, "why": f"check request ended {h.status} "
                    f"with {len(toks)} tokens, {h.preempts} preemptions"}
    if held is None or len(taps) < tap_steps:
        return {"ok": False, "why": f"{len(taps)} of {tap_steps} decode "
                "steps were tapped before the request ended, or its rows "
                "were not read in time"}
    weights = reference.weights_of(model)
    ref_fields = reference.fields_of(fields)
    limits = dict(reference.LIMITS)
    # a request's last step consumed all but the last of its tokens
    seqs = [np.concatenate([p, toks[:-1]]).astype(np.int64)
            for p, toks in zip(prompts, served)]
    steps = [(pos, *model.unpack_tap(tap)) for pos, tap in taps]
    at = len(prompts[0]) - 1

    def full(j, **fault):
        """Readings of check request ``j`` against the full forward over
        its ids (made to get ``fault`` wrong): the served tokens'
        deficits, and for the tapped request the tapped logits and the
        held rows."""
        start = len(prompts[j]) - 1
        logits, cached = reference.forward(
            weights, ref_fields, seqs[j],
            np.arange(start, start + n_new), **fault)
        out = {"deficits": reference.deficits(logits, served[j])}
        if j == 0:
            out["logit_rms_steps"] = [
                reference.rel_rms(tail["logits"], logits[pos - at])
                for pos, _, tail in steps]
            out["logit_rms"] = float(min(out["logit_rms_steps"]))
            out["cached_rows"] = max(
                float(np.median(reference.row_errors(
                    np.concatenate(got, 1),
                    np.concatenate(want, 1)[:len(got[0])])))
                for got, want in zip(held, cached))
        return out

    def against_full(readings):
        """The limits' readings of the full forwards' ``readings``, and
        for the notes the deficits' quantiles."""
        pooled = np.concatenate([r["deficits"] for r in readings])
        out = {"margin_p90": float(np.percentile(pooled, 90))}
        notes = {f"p{q}": float(np.percentile(pooled, q))
                 for q in (50, 75, 90, 99, 100)}
        notes["over_margin"] = float(np.mean(pooled > reference.MARGIN))
        for r in readings:
            out.update({k: v for k, v in r.items()
                        if k in ("logit_rms", "cached_rows")})
            if "logit_rms_steps" in r:
                notes["logit_rms_steps"] = r["logit_rms_steps"]
        return out, notes

    def replayed(**fault):
        reads = [reference.replay_step(weights, ref_fields, layers, pos,
                                       held, **fault)
                 for pos, layers, _ in steps]
        return {k: max(r[k] for r in reads) for k in reads[0]}

    whole, margins = against_full(
        [full(j) for j in range(_CHECK_REQUESTS)])
    program = dict(whole, **replayed())
    tapped_live = all(float(tail["active"][0]) == 1.0
                      for _, _, tail in steps)
    out = {"ok": bool(live >= shape["slots"] - 1 and tapped_live
                      and all(program[k] <= limits[k] for k in limits)),
           "worst": program, "limits": limits, "margins": margins,
           "tokens_each": n_new,
           "prompt_tokens": [len(p) for p in prompts],
           "tapped_positions": [pos for pos, _, _ in steps],
           "load": {"live_slots_min": live}}
    if with_faults:
        planted = {name: replayed(**kw)
                   for name, kw in reference.FAULTS.items()}
        for name in _FULL_FAULTS:
            planted[name + "_full"], _ = against_full(
                [full(0, **reference.FAULTS[name])])
        for by in planted.values():
            by["breaks"] = sorted(k for k in by
                                  if by[k] > limits.get(k, np.inf))
        out["planted"] = planted
    return out


def run(run):
    import jax.numpy as jnp

    from paddle_tpu.models import Xing
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

    # the route counters move when a program is traced
    kernels_before = metrics.snapshot("serving.kernel.")
    degrade_before = metrics.snapshot("resilience.degrade.")
    model = build_model(Xing, xing_config(fields), dtype,
                        weights_seed(cell.workload, run.seed))
    model.eval()
    mark("build_s")
    engine = ServingEngine(
        model, temperature=0.0, ready=False, dtype=jnp.dtype(dtype),
        max_batch=shape["slots"], block_size=shape["block_size"],
        max_seq_len=shape["max_seq_len"], bucket_cap=shape["bucket_cap"],
        # a rehearsal on the CPU runs the same kernels interpreted
        paged_kernel="pallas" if run.rehearsal else None)
    try:
        engine.warmup()
        mark("engine_and_warmup_s")
        serve._warm_traffic(engine, mix, shape["slots"],
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
        failures = []  # how each failed request ended, for the notes
        for r in counted:
            toks = r.handle.tokens() if r.handle is not None else []
            if not (r.complete and str(r.handle.status) == "DONE"
                    and len(toks) == r.n_new
                    and all(0 <= int(t) < vocab for t in toks)):
                failures.append({
                    "index": r.index, "refused": r.refused,
                    "status": str(r.handle.status) if r.handle else None,
                    "preempts": r.handle.preempts if r.handle else None,
                    "n_new": r.n_new, "tokens": len(toks),
                    "stamped": len(r.times)})
        failed = len(failures)
        kernels = harness.registry_delta(
            kernels_before, metrics.snapshot("serving.kernel."))
        degraded = harness.registry_delta(
            degrade_before, metrics.snapshot("resilience.degrade."))

        def taken(name):
            return kernels.get(f"serving.kernel.{name}.pallas", 0) > 0 \
                and kernels.get(f"serving.kernel.{name}.plain", 0) == 0

        route_ok = (taken("mla_decode") and taken("moe_gmm")
                    and not any(degraded.values()))
        t_ref = time.perf_counter()
        ref = reference_check(
            engine, model, fields, mix, shape,
            int(cell.workload.get("check_output", 512)),
            int(cell.workload.get("tap_steps", 6)),
            run.rehearsal)
        ref["seconds"] = time.perf_counter() - t_ref
    finally:
        engine.close()

    stats = serve._client_stats(records, t0, t1, open_loop)
    window = [s for t, s in load.samples if t0 <= t < t1]
    delta = harness.registry_delta(before, after)
    stamps = np.sort([t for r in records for t in r.times if t0 <= t < t1])
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
        "ended": {k: delta.get("serving." + k, 0) for k in (
            "completed", "cancelled", "timeout", "shed", "errors",
            "preempt", "callback_errors", "rejected")},
        "failures": failures[:8],
        "longest_silence_s": float(np.diff(stamps).max())
        if len(stamps) > 1 else None,
        "latent_bytes": cache.pool_bytes(),
        "moe": {k: delta.get("serving.moe." + k, 0)
                for k in ("rows", "experts_hit", "max_rows")},
        "decode": {k: delta.get("serving.decode." + k, 0)
                   for k in ("ahead", "in_order", "context_tokens")},
        "step": blockdiff._step_notes(delta),
        "window_compiles": delta.get("xla.compile.count", 0)}
    for key in ("ttft_ms", "itl_ms"):
        v = stats[key]
        if v:
            notes[key] = {"n": len(v), "p50": harness.percentile(v, 50),
                          "p95": harness.percentile(v, 95)}
    return {"correct": failed == 0 and route_ok and ref["ok"],
            "attempted": len(counted), "failed": failed,
            "end_to_end": end_to_end, "memory_peak_bytes": memory_peak,
            "notes": notes,
            "ctx": {"counters": delta, "client": stats, "trace": reduced,
                    "kv_active_share": window, "seconds": run.seconds}}
