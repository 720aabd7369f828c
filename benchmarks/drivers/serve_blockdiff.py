"""Driver of the block-diffusion serving cells: a ``ServingEngine`` over
an ``sdar_moe`` configuration (``paddle_tpu.models.SDAR``: sparse
experts, generation by diffusion over blocks), loaded by
``client.Client`` with the cell's traffic mix. The timeline, the window,
the client statistics and the warm-up traffic are ``drivers/serve.py``'s,
imported and not copied; what differs is the model, the reference and
what ``correct`` compares.

Tokens arrive a block at a time, so a gap between two tokens of a
request is either zero or a block's forwards: the cell reports
``serve_tok_s`` and ``setup_s`` and no ``itl_p95_ms``.

``correct``: every judged request DONE with its token count and ids in
the vocabulary; both new kernels on the Pallas route (the grouped expert
matmul and the block attention) and nothing degraded; and, after the
window, at the timed load, the reference check: two more requests of the
mix are served through the same engine while every other slot runs
other prompts of the mix (``serve_under_load``), so that their forwards
are the window's: every slot live, 16 rows an expert on average and the
largest group past one tile of the grouped matmul (both held), contexts
as long as the mix's prompts. ``Scheduler.block_observer`` records every
slot-forward of the two. The recorded block ids go to the plain
reference (``reference/sdar_moe_blockdiff.py``) as context + block, and
at every denoising and commit forward (a) the program's logit at each
token it chose against the reference's logit there and (c) its
probability against the reference's, each within its limits
(``reference.LIMITS``: which reading of a run each limit holds, and
what it lies between; (b) that token against the reference's maximum
is read into the notes, and why no limit holds it is said there), (d) the
positions unmasked are the rule's, given the program's own
probabilities. Block ``n + 1``'s logits hang on block ``n``'s committed
keys and values, so this holds prefill and decoding through the cache to
the full forward. And (e) what each layer's router gave inside those
very block steps (the program returns it beside its input) is the
reference's float32 router's on that input, and (f) what each expert
layer gave there is the reference's experts' on that input under that
routing (``layer_checks``): what the logits cannot hold, because the
program's bfloat16 activations move the router's input by as much as a
bfloat16 router would move its output, and the logits by more than
experts kept in int8 would. A traced run and the rehearsal
also read the same records against the reference made to get something
wrong (``_FAULTS``, a bfloat16 router): notes only.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmarks import client as client_mod
from benchmarks import harness, traffic
from benchmarks.build import build_model
from benchmarks.reference import sdar_moe_blockdiff as reference

serve = harness.load_module(os.path.join(harness.BENCH, "drivers",
                                         "serve.py"))

_CHECK_REQUESTS = 2
_CHECK_OUTPUT = 18     # tokens a check request generates: 5 blocks
# what the reference is made to get wrong, each read on the same records
# (a traced run and the rehearsal; PERF.md section 6 has the readings)
_FAULTS = {"dropped_expert": {"drop_top": True},
           "int8_experts": {"expert_dtype": "int8"}}


def sdar_config(fields, mix):
    from paddle_tpu.models import SDARConfig

    return SDARConfig(
        vocab_size=fields["vocab_size"], hidden_size=fields["hidden_size"],
        num_layers=fields["num_hidden_layers"],
        num_heads=fields["num_attention_heads"],
        num_kv_heads=fields["num_key_value_heads"],
        head_dim=fields["head_dim"], num_experts=fields["num_experts"],
        num_experts_per_tok=fields["num_experts_per_tok"],
        moe_intermediate_size=fields["moe_intermediate_size"],
        norm_topk_prob=fields["norm_topk_prob"],
        max_position_embeddings=fields["max_position_embeddings"],
        rms_norm_eps=fields["rms_norm_eps"],
        rope_theta=float(fields["rope_theta"]),
        initializer_range=fields["initializer_range"],
        block_length=int(mix["block_length"]),
        mask_token_id=fields["mask_token_id"],
        denoise_steps=int(mix["denoise_steps"]))


def serve_under_load(engine, mix, first, shape):
    """Serve ``_CHECK_REQUESTS`` requests of the mix (its prompts
    ``first``... of the cycle) while the engine's other slots run other
    prompts of the mix, every slot-forward of the check requests
    recorded. The others are submitted first and the queue is served in
    order, so a check request is admitted to an engine whose other slots
    are all live, as in the window; they generate until they are
    withdrawn, when the check requests are done. Returns (prompts,
    handles, records) of the check requests."""
    sched = engine.scheduler
    room = shape["max_seq_len"] - int(mix.p["block_length"])
    others = []
    for j in range(shape["slots"] - _CHECK_REQUESTS):
        prompt, _ = mix.request(first + j)
        others.append(engine.submit(prompt,
                                    max_new_tokens=room - len(prompt)))
    skip = {h.rid for h in others}
    records = []
    sched.block_observer = lambda rec: \
        rec["rid"] in skip or records.append(rec)
    try:
        prompts = [mix.request(first + len(others) + j)[0]
                   for j in range(_CHECK_REQUESTS)]
        handles = [engine.submit(p, max_new_tokens=_CHECK_OUTPUT)
                   for p in prompts]
        for h in handles:
            h.result(timeout=600)
    finally:
        sched.block_observer = None
        for h in others:
            h.cancel()
    rids = {h.rid for h in handles}
    return prompts, handles, [r for r in records if r["rid"] in rids]


def layer_checks(records, weights, fields, faults):
    """(e) and (f), of the timed program: what every layer's router and
    expert layer gave in the recorded block steps for the check
    requests' rows, against the reference's float32 router and its
    experts fed the same input, the program's own (the experts under
    the program's own routing). Returns the two readings of the program,
    the largest over the layers, the rows (e) decided, and the same
    readings with each of ``faults`` (and a bfloat16 router) planted in
    the reference."""
    import jax.numpy as jnp

    top_k, norm = fields["num_experts_per_tok"], fields["norm_topk_prob"]
    worst = {"router": 0.0, "experts": 0.0}
    planted = {name: {"experts": 0.0} for name in faults}
    if faults:
        planted["bf16_router"] = {"router": 0.0}
    decided = 0
    # [2, layers, the records' rows, hidden or k]
    io, route = (np.concatenate(
        [np.asarray(rec["moe"][part][:, :, rec["moe_rows"]])
         for rec in records], axis=2) for part in range(2))
    for i, w in enumerate(weights[1]):
        m, y, gate, idx = io[0, i], io[1, i], route[0, i], \
            route[1, i].astype(np.int32)
        err, n = reference.compare_router(gate, idx, m, w["router"], top_k,
                                          norm)
        decided += n
        worst["router"] = max(worst["router"], err)
        worst["experts"] = max(worst["experts"], reference.compare_experts(
            y, m, gate, idx, w))
        for name, kw in faults.items():
            planted[name]["experts"] = max(
                planted[name]["experts"],
                reference.compare_experts(y, m, gate, idx, w, **kw))
        if faults:
            planted["bf16_router"]["router"] = max(
                planted["bf16_router"]["router"], reference.compare_router(
                    gate, idx, m, w["router"], top_k, norm,
                    dtype=jnp.bfloat16)[0])
    return worst, decided, planted


def reference_check(engine, model, fields, mix, shape, rehearsal,
                    with_faults):
    """The check of the module docstring. ``with_faults`` also reads
    every record against the planted faults (``_FAULTS``, and a bfloat16
    router for (e)): the notes say which limits each breaks. A
    rehearsal's few slots fill no group past one tile."""
    from paddle_tpu.kernels.pallas.moe_gmm import tile_rows

    width = int(mix.p["block_length"])
    slots = shape["slots"]
    # requests the window never sent, at a place in the mix's cycle of
    # lengths that the seed draws
    first = mix.n * 1000 + int(np.random.default_rng(
        [mix.seed, 99]).integers(mix.n))
    prompts, handles, records = serve_under_load(engine, mix, first, shape)
    served = [[int(t) for t in h.tokens()] for h in handles]
    for h, toks in zip(handles, served):
        if str(h.status) != "DONE" or len(toks) != _CHECK_OUTPUT:
            return {"ok": False, "why": f"check request ended {h.status} "
                                        f"with {len(toks)} tokens"}
    by_rid = {h.rid: (p, toks) for h, p, toks
              in zip(handles, prompts, served)}
    pad_to = -(-(max(map(len, prompts)) + _CHECK_OUTPUT + width)
               // width) * width
    weights = reference.weights_of(model)
    ref_fields = reference.fields_of(dict(fields, block_length=width))
    faults = _FAULTS if with_faults else {}
    read = {name: {k: [] for k in reference.KINDS}
            for name in ("program", *faults)}
    rule_ok, committed = True, {}
    for rec in records:
        prompt = by_rid[rec["rid"]][0]
        # context: the prompt's whole blocks and the blocks committed
        # since, as the engine's own records gave them
        context = list(prompt[:len(prompt) // width * width]) \
            + committed.get(rec["rid"], [])
        if len(context) != rec["seq_len"]:
            return {"ok": False, "why": "a record's seq_len is not the "
                                        "committed length"}
        seq = np.zeros((pad_to,), np.int64)
        seq[:len(context)] = context
        seq[len(context):len(context) + width] = rec["ids"]
        rows = np.arange(len(context), len(context) + width)
        for name, kw in (("program", {}), *faults.items()):
            errs = reference.compare_forward(rec, reference.logits(
                weights, ref_fields, seq, rows=rows, **kw))
            for k in reference.KINDS:
                read[name][k].extend(errs[k])
        # (d) the rule, from the program's own probabilities
        if rec["commit"]:
            rule_ok = rule_ok and not rec["masked"].any() \
                and not rec["unmasked"].any()
            committed.setdefault(rec["rid"], []).extend(
                int(t) for t in rec["ids"])
        else:
            opened = width - (len(prompt) % width if not
                              committed.get(rec["rid"]) else 0)
            done = opened - int(rec["masked"].sum())
            plan = reference.unmask_counts(opened,
                                           int(mix.p["denoise_steps"]))
            n = plan[sum(np.cumsum(plan) <= done)]
            rule_ok = rule_ok and bool(np.array_equal(
                reference.pick_unmasked(rec["probs"], rec["masked"], n),
                rec["unmasked"]))
    # the tokens handed out are the committed blocks' (less what the
    # prompt gave and what runs past max_new_tokens)
    for rid, (prompt, toks) in by_rid.items():
        given = len(prompt) % width
        rule_ok = rule_ok and \
            committed.get(rid, [])[given:given + len(toks)] == toks
    worst = {name: reference.readings(by) for name, by in read.items()}
    layers, decided, planted = layer_checks(records, weights, fields, faults)
    worst["program"].update(layers)
    for name, by in planted.items():
        worst.setdefault(name, {}).update(by)
    # the load the recorded forwards ran under: the other slots live,
    # and the largest group of the grouped matmul past one tile of rows
    tm = tile_rows(slots * width * fields["num_experts_per_tok"],
                   fields["num_experts"])
    live = [rec["batch"] for rec in records]
    largest = [int(rec["expert_rows"].max()) for rec in records]
    limits = reference.LIMITS
    ok = len(records) >= 4 * 3 * _CHECK_REQUESTS // 2 and rule_ok \
        and decided > 0 and min(live) >= slots - 1 \
        and (rehearsal or min(largest) > tm) \
        and all(worst["program"][k] <= limits[k] for k in limits)
    out = {"ok": bool(ok), "forwards": len(records),
           "rule_ok": bool(rule_ok), "worst": worst["program"],
           "limits": limits, "router_rows": decided,
           "prompt_tokens": [len(p) for p in prompts],
           "load": {"live_slots_min": min(live), "tile_rows": tm,
                    "largest_group_min": min(largest),
                    "largest_group_max": max(largest)}}
    if with_faults:
        out["planted"] = {name: dict(by, breaks=[
            k for k in by if by[k] > limits.get(k, np.inf)])
            for name, by in worst.items() if name != "program"}
    return out


def _step_notes(delta):
    """What a scheduler step of the window cost by phase (mean ms a
    step), and how many ran: which part a slow run was slow in."""
    steps = (delta.get("serving.step_us") or {}).get("count", 0)
    if not steps:
        return {}
    out = {"steps": steps, "prefills": (delta.get(
        "serving.phase.prefill_forward_us") or {}).get("count", 0),
        "step_ms": delta["serving.step_us"]["sum"] / steps / 1e3}
    for phase in ("decode_dispatch", "decode_readback", "decode_prepare",
                  "decode_emit", "block_commit", "block_unmask",
                  "prefill_forward", "admit", "step_end",
                  "engine_lock_wait"):
        h = delta.get(f"serving.phase.{phase}_us")
        if h:
            out[phase + "_ms"] = h["sum"] / steps / 1e3
    return out


def run(run):
    import jax.numpy as jnp

    from paddle_tpu.models import SDAR
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
    model = build_model(SDAR, sdar_config(fields, cell.traffic), dtype,
                        run.seed)
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
        route_ok = (kernels.get("serving.kernel.pallas", 0) > 0
                    and kernels.get("serving.kernel.dense", 0) == 0
                    and kernels.get("serving.kernel.moe_gmm.pallas", 0) > 0
                    and kernels.get("serving.kernel.moe_gmm.plain", 0) == 0
                    and (run.rehearsal
                         or kernels.get("serving.kernel.interpret", 0) == 0)
                    and not any(degraded.values()))
        t_ref = time.perf_counter()
        ref = reference_check(engine, model, fields, mix, shape,
                              run.rehearsal, run.trace or run.rehearsal)
        ref["seconds"] = time.perf_counter() - t_ref
    finally:
        engine.close()

    stats = serve._client_stats(records, t0, t1, open_loop)
    window = [s for t, s in load.samples if t0 <= t < t1]
    delta = harness.registry_delta(before, after)
    stamps = np.sort([t for r in records for t in r.times if t0 <= t < t1])
    end_to_end = {"setup_s": setup_s,
                  "serve_tok_s": stats["tokens_in_window"] / run.seconds}
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
        # why a run failed, where one does: how the window's requests
        # ended (a stalled step once made overload control shed 28 of a
        # queue of 64: the driver's check of PR 28), the first failures,
        # and the longest the clients went without a token
        "ended": {k: delta.get("serving." + k, 0) for k in (
            "completed", "cancelled", "timeout", "shed", "errors",
            "preempt", "callback_errors", "rejected")},
        "failures": failures[:8],
        "longest_silence_s": float(np.diff(stamps).max())
        if len(stamps) > 1 else None,
        "step": _step_notes(delta),
        "window_compiles": delta.get("xla.compile.count", 0)}
    if stats["ttft_ms"]:
        v = stats["ttft_ms"]
        notes["ttft_ms"] = {"n": len(v), "p50": harness.percentile(v, 50),
                            "p95": harness.percentile(v, 95)}
    return {"correct": failed == 0 and route_ok and ref["ok"],
            "attempted": len(counted), "failed": failed,
            "end_to_end": end_to_end, "memory_peak_bytes": memory_peak,
            "notes": notes,
            "ctx": {"counters": delta, "client": stats, "trace": reduced,
                    "kv_active_share": window, "seconds": run.seconds}}
