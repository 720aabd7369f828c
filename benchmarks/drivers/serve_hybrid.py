"""Driver of the hybrid serving cells: a ``ServingEngine`` over a ``jamba``
configuration (``paddle_tpu.models.Jamba``: state-space layers with an
attention layer among every few, so a slot holds recurrent state beside
its pages of K and V), loaded by ``client.Client`` with the cell's
traffic mix. The timeline, the window, the client statistics and the
warm-up traffic are ``drivers/serve.py``'s, imported and not copied;
what differs is the model, the reference and what ``correct`` compares.

``correct``: every judged request DONE with its token count and ids in
the vocabulary; the prefill's scan, the decode step's state update and
the paged attention on the Pallas route and nothing degraded; and, after
the window, at the timed load, the reference check: two more requests of
the mix are served through the same engine while every other slot runs
other prompts of the mix (``serve_under_load``), each generating
``check_output`` tokens (512 in the cell), so that their steps are the
window's: every slot live, state updated in place a step ahead. Then

(a) the served tokens against the plain reference's logits over prompt +
    answer (``reference.margin_check``: the reference's maximum at a
    position less its logit of the served token, as a share of the
    logits' scale), which holds the prefill (the chunked scan at a
    padded bucket) and then every decode step through both caches to
    the full forward: token ``j + 1``'s logits hang on the state that
    step ``j`` left;
(b) the slot's ``h`` a few steps after its prefill (``start_h``), and its
    ``h`` and convolution's tail after the request's last step
    (``end_h``, ``end_tail``), read from the cache under ``pool_lock``
    (the slot is not used again before), against the reference's after
    the same tokens, by relative RMS a state-space layer, the largest.
    The program's activations are bfloat16 and a layer's state hangs on
    every layer below, so these read percents: they hold what reaches
    the state from outside the recurrence (a slot's last state left in
    place, padding that advances ``h``), early, before it has decayed;
(c) ``replay_h``: the recurrence on the program's own inputs. The decode
    program reports, for one slot, what moved each state-space layer's
    ``h`` in that very step (delta, c, B: the debug tap of
    ``Jamba.paged_decode_step``); from the early state of (b) the
    reference replays those steps in float32, and the slot's final ``h``
    must be that to float32's rounding: what (b) cannot hold, because a
    state rounded to bfloat16 every step moves ``h`` by less than the
    program's bfloat16 activations do. And ``fed_inputs``: what the
    program reported against what the reference's full forward fed its
    own recurrence at the same positions, by relative RMS a layer, the
    largest of delta, c and B: a replay proves nothing where the report
    is not what the model computes there.

``reference.LIMITS`` says which readings each limit lies between. A
traced run and the rehearsal also read the same records against the
reference made to get something wrong (``_FAULTS``): ``h`` rounded to
bfloat16 after every step (in the replay); the prompt's padding run as
real tokens, so that it advances ``h``; the state of another request
left in the slot; the reports of the layer before read as this layer's.
Notes only; PERF.md section 6 (PR 32) has the readings.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmarks import client as client_mod
from benchmarks import harness, traffic
from benchmarks.build import build_model
from benchmarks.reference import jamba_hybrid as reference

blockdiff = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                             "serve_blockdiff.py"))
serve = blockdiff.serve

_CHECK_REQUESTS = 2
_FAULTS = ("bf16_state", "padding_advances", "stale_state", "other_layer")


# the configuration file's keys that the model's dataclass spells otherwise
_RENAMED = {"num_hidden_layers": "num_layers",
            "num_attention_heads": "num_heads",
            "num_key_value_heads": "num_kv_heads"}


def jamba_config(fields):
    """``JambaConfig`` of a configuration file: every key the dataclass
    has, under its name there."""
    import dataclasses

    from paddle_tpu.models import JambaConfig

    known = {f.name for f in dataclasses.fields(JambaConfig)}
    named = {_RENAMED.get(k, k): v for k, v in fields.items()}
    return JambaConfig(**{k: v for k, v in named.items() if k in known})


def _held(cache, slot):
    """What ``slot`` holds, as the reference lays it out: (h [state
    layers, E, N], tail [state layers, K-1, E]). The caller holds
    ``pool_lock``: the engine's thread donates the arrays every step."""
    return (np.array(cache.ssm_state[:, slot]).transpose(0, 2, 1),
            np.array(cache.conv_state[:, :, slot], dtype=np.float32))


def serve_under_load(engine, mix, first, shape, n_new):
    """Serve ``_CHECK_REQUESTS`` requests of the mix (its prompts
    ``first``... of the cycle), ``n_new`` tokens each, while the engine's
    other slots run other prompts of the mix: those are submitted first
    and the queue is served in order, so a check request is admitted to
    an engine whose other slots are all live, as in the window; they
    generate until they are withdrawn, when the check requests are done.
    Returns (prompts, handles, a dict a check request, the fewest slots
    that were live while they ran). A dict: ``early`` (decode steps
    done, h) read soon after the prefill, ``end`` (h, tail) when the
    request had finished, and for the first request ``fed``, what its
    slot's recurrence was fed in every decode step after ``early``."""
    cache, sched = engine.cache, engine.scheduler
    others = []
    for j in range(shape["slots"] - _CHECK_REQUESTS):
        prompt, _ = mix.request(first + j)
        others.append(engine.submit(
            prompt, max_new_tokens=shape["max_seq_len"] - len(prompt)))
    live, fed = [], []
    try:
        prompts = [mix.request(first + len(others) + j)[0]
                   for j in range(_CHECK_REQUESTS)]
        handles = [engine.submit(p, max_new_tokens=n_new) for p in prompts]
        seen = [{"slot": -1} for _ in handles]
        while not all(h._req.done for h in handles):  # noqa: SLF001
            for h, prompt, rec in zip(handles, prompts, seen):
                slot = h._req.slot  # noqa: SLF001 — the slot to read
                if slot < 0 or "early" in rec:
                    continue
                rec["slot"] = slot
                # state, lengths and the observer's list agree under
                # the lock: a decode step moves all three inside it
                with cache.pool_lock:
                    if cache.state_fresh[slot]:
                        continue  # admitted, not prefilled yet
                    steps = int(cache.seq_lens[slot]) - len(prompt)
                    rec["early"] = (steps, _held(cache, slot)[0])
                    if rec is seen[0]:
                        sched.state_observer = (slot, fed)
            if all("early" in rec for rec in seen):
                live.append(sum(cache._live))  # noqa: SLF001
            time.sleep(0.002)
        sched.state_observer = None
        # nothing is queued, so the slots stay as their requests left
        with cache.pool_lock:
            for rec in seen:
                rec["end"] = _held(cache, rec["slot"])
        need = n_new - 1 - seen[0]["early"][0]
        seen[0]["fed"] = np.stack([np.asarray(f) for f in fed[:need]]) \
            if 0 < need <= len(fed) else None
    finally:
        sched.state_observer = None
        for h in others:
            h.cancel()
    return prompts, handles, seen, min(live) if live else 0


def reference_check(engine, model, fields, mix, shape, n_new, with_faults):
    """The check of the module docstring. ``with_faults`` also reads
    every record against the planted faults: the notes say which limits
    each breaks."""
    from paddle_tpu.serving.bucketing import bucket_length

    first = mix.n * 1000 + int(np.random.default_rng(
        [mix.seed, 99]).integers(mix.n))
    prompts, handles, seen, live = serve_under_load(engine, mix, first,
                                                    shape, n_new)
    served = [[int(t) for t in h.tokens()] for h in handles]
    for h, toks in zip(handles, served):
        if str(h.status) != "DONE" or len(toks) != n_new or h.preempts:
            return {"ok": False, "why": f"check request ended {h.status} "
                    f"with {len(toks)} tokens, {h.preempts} preemptions"}
    fed = seen[0]["fed"]
    if fed is None or not fed[:, :, -1].all():
        return {"ok": False, "why": "the decode steps' reports do not "
                "cover the first check request's steps"}
    weights = reference.weights_of(model)
    ref_fields = reference.fields_of(fields)
    limits = dict(reference.LIMITS, margin=reference.MARGIN)
    slow = reference.slowest(weights)

    def read(j, ids, at, **kw):
        """The readings of check request ``j`` against the full forward
        over ``ids``, whose logits from position ``at`` on chose its
        tokens; the forward's final state; and what moved its ``h`` at
        the positions of the decode steps after ``early``."""
        steps, early = seen[j]["early"]
        logits, h, tail, h_early, moved = reference.forward(
            weights, ref_fields, ids, np.arange(at, at + n_new),
            snap=at + 1 + steps, fed=True, **kw)
        end_h, end_tail = seen[j]["end"]
        return ({"margin": reference.margin_check(logits, 1, served[j])[0],
                 "start_h": reference.rel_rms(early, h_early),
                 "start_h_slow": reference.rel_rms(early, h_early, slow),
                 "end_h": reference.rel_rms(end_h, h),
                 "end_tail": reference.rel_rms(end_tail, tail)},
                (h, tail), moved)

    def replayed(**kw):
        return reference.rel_rms(seen[0]["end"][0], reference.replay(
            weights, seen[0]["early"][1], fed, **kw))

    e, n = next(w["A_log"].shape for w in weights[1] if "A_log" in w)

    def reported(fed, moved):
        """The program's reports ``fed`` [steps, layers, 2 E + N + 1]
        against the reference's own ``moved`` [layers, steps, 2 E + N]:
        the largest of delta, c and B."""
        got = fed.transpose(1, 0, 2)
        return max(reference.rel_rms(got[..., part], moved[..., part])
                   for part in (slice(0, e), slice(e, 2 * e),
                                slice(2 * e, 2 * e + n)))

    def worst(readings):
        return {k: max(r[k] for r in readings) for k in readings[0]}

    # the state a request's last step left is the state after its
    # prompt and all but the last of its tokens
    seqs = [np.concatenate([p, toks[:-1]]).astype(np.int64)
            for p, toks in zip(prompts, served)]
    program, states, moved = zip(*(read(j, seq, len(prompts[j]) - 1)
                                   for j, seq in enumerate(seqs)))
    program = dict(worst(program), replay_h=replayed(),
                   fed_inputs=reported(fed, moved[0]))
    out = {"ok": bool(live >= shape["slots"] - 1
                      and all(program[k] <= limits[k] for k in limits)),
           "worst": program, "limits": limits, "tokens_each": n_new,
           "prompt_tokens": [len(p) for p in prompts],
           "early_steps": [rec["early"][0] for rec in seen],
           "replayed_steps": len(fed), "load": {"live_slots_min": live}}
    if with_faults:
        planted = {"padding_advances": [], "stale_state": []}
        for j, (seq, prompt) in enumerate(zip(seqs, prompts)):
            at = len(prompt) - 1
            pad = bucket_length(len(prompt), shape["block_size"],
                                shape["bucket_cap"],
                                max_len=shape["max_seq_len"]) - len(prompt)
            planted["padding_advances"].append(read(j, np.concatenate(
                [prompt, np.zeros((pad,), np.int64), seq[len(prompt):]]),
                at + pad)[0] if pad else dict(program))
            planted["stale_state"].append(
                read(j, seq, at, h0=states[j - 1])[0])
        out["planted"] = {name: worst(readings)
                          for name, readings in planted.items()}
        out["planted"]["bf16_state"] = {"replay_h": replayed(h_bits=7)}
        out["planted"]["other_layer"] = {
            "fed_inputs": reported(np.roll(fed, 1, axis=1), moved[0])}
        for by in out["planted"].values():
            by["breaks"] = [k for k in by if by[k] > limits[k]]
    return out


def run(run):
    import jax.numpy as jnp

    from paddle_tpu.models import Jamba
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
    model = build_model(Jamba, jamba_config(fields), dtype, run.seed)
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

        route_ok = (kernels.get("serving.kernel.pallas", 0) > 0
                    and kernels.get("serving.kernel.dense", 0) == 0
                    and taken("ssm_scan") and taken("ssm_update")
                    and (run.rehearsal
                         or kernels.get("serving.kernel.interpret", 0) == 0)
                    and not any(degraded.values()))
        t_ref = time.perf_counter()
        ref = reference_check(
            engine, model, fields, mix, shape,
            int(cell.workload.get("check_output", 512)),
            run.trace or run.rehearsal)
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
        "state_bytes": cache.state_bytes(),
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
