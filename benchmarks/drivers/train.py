"""Driver of the training cells: ``paddle.jit.TrainStep`` over a GPT
configuration on a cycling set of seeded batches.

Timeline: process start .. build, two warm-up steps (the first compiles,
the second sees the donated buffers' final layouts) .. [window: steps are
dispatched with at most two in flight until --seconds have passed, then
the last one is waited for] .. correctness .. result. The window's length
is the time to that last ``block_until_ready``, so the rate is over all
the work and all the time. A traced run spends the last quarter of its
window on steps that are each waited for (``step_ms``).
"""

from __future__ import annotations

import contextlib
import re
import time

import numpy as np

from benchmarks import harness, traffic
from benchmarks.build import build_model
from benchmarks.reference import gpt2 as reference

_IN_FLIGHT = 2


def _gpt_config(fields):
    from paddle_tpu.models import GPTConfig

    return GPTConfig(
        vocab_size=fields["vocab_size"] + fields["assumed"].get(
            "vocab_padding", 0),
        max_position_embeddings=fields["n_positions"],
        hidden_size=fields["n_embd"], num_layers=fields["n_layer"],
        num_heads=fields["n_head"],
        layer_norm_epsilon=fields["layer_norm_epsilon"],
        initializer_range=fields["initializer_range"])


def run(run):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.models import GPT
    from paddle_tpu.profiler import metrics

    cell = run.cell
    fields = cell.config
    hyper = cell.workload["optimizer"]
    mix = cell.traffic
    config = _gpt_config(fields)
    dtype = fields["assumed"]["dtype"]

    model = build_model(GPT, config, dtype, run.seed)
    opt = optimizer.AdamW(
        learning_rate=hyper["learning_rate"], parameters=model.parameters(),
        grad_clip=nn.ClipGradByGlobalNorm(hyper["clip_global_norm"]))
    step = paddle.jit.TrainStep(model, opt, lambda m, ids: m.loss(ids, ids))
    host_batches = traffic.train_batches(mix, run.seed, fields["vocab_size"])
    tokens_per_step = host_batches[0].size
    n_params = sum(int(p.size) for p in model.parameters()) \
        - int(model.wpe.weight.size)

    losses = []  # device scalars; read after the window

    def one_step(i):
        # the batch is fed from the host each step, as a data loader does
        losses.append(step(paddle.to_tensor(
            host_batches[i % len(host_batches)]))._data)

    for i in range(2):
        one_step(i)
    jax.block_until_ready(losses[-1])
    tracer = run.trace_slice()
    annotate = tracer.annotate if tracer \
        else (lambda _name: contextlib.nullcontext())

    setup_s = harness.process_age_s()
    before = metrics.snapshot()
    t0 = time.perf_counter()
    async_until = t0 + run.seconds * (0.75 if tracer else 1.0)
    if tracer:
        tracer.schedule(t0 + 0.3 * run.seconds, min(3.0, 0.2 * run.seconds))
    n = len(losses)
    while time.perf_counter() < async_until:
        with annotate("train.step_call"):
            one_step(n)
        n += 1
        with annotate("train.wait_in_flight"):
            jax.block_until_ready(losses[-1 - _IN_FLIGHT])
    jax.block_until_ready(losses[-1])
    t_async = time.perf_counter()
    async_steps = n - 2
    blocked = 0
    while tracer and time.perf_counter() < t0 + run.seconds:
        one_step(n)
        jax.block_until_ready(losses[-1])
        n += 1
        blocked += 1
    t1 = time.perf_counter()
    delta = harness.registry_delta(before, metrics.snapshot())
    steps = n - 2
    memory_peak = run.memory_peak_bytes()
    reduced = tracer.finish() if tracer else None

    # -- correct ---------------------------------------------------------
    values = [float(np.asarray(v)) for v in losses]
    kernels = sorted(set(re.findall(
        r'kernel_name = "(\w+)"',
        step.lower(paddle.to_tensor(host_batches[0])).as_text())))
    want = [] if run.rehearsal else ["flash_dkv", "flash_dq", "flash_fwd"]
    # the first loss again from the plain reference: the same seed gives
    # the same initial weights (one deterministic program), so nothing
    # has to be kept from before the first step
    ref_loss = reference.loss(
        reference.weights_of(build_model(GPT, config, dtype, run.seed)),
        {"num_heads": config.num_heads,
         "layer_norm_epsilon": config.layer_norm_epsilon},
        host_batches[0])
    rel = abs(values[0] - ref_loss) / abs(ref_loss)
    fell = (len(values) >= 8
            and np.mean(values[-4:]) < np.mean(values[:4]))
    correct = (bool(np.isfinite(values).all()) and fell
               and rel <= reference.LOSS_TOLERANCE
               and set(want) <= set(kernels))
    window_s = t1 - t0
    tok_s = steps * tokens_per_step / window_s
    notes = {"steps": steps, "window_s": window_s,
             "tokens_per_step": tokens_per_step,
             "loss_first": values[0], "loss_reference": ref_loss,
             "loss_rel_err": rel, "loss_tolerance": reference.LOSS_TOLERANCE,
             "loss_first4": float(np.mean(values[:4])),
             "loss_last4": float(np.mean(values[-4:])),
             "kernels": kernels, "params": n_params,
             "window_compiles": delta.get("xla.compile.count", 0)}
    return {"correct": correct, "attempted": steps,
            "failed": int(sum(not np.isfinite(v) for v in values)),
            "end_to_end": {"setup_s": setup_s, "train_tok_s": tok_s},
            "memory_peak_bytes": memory_peak, "notes": notes,
            "ctx": {"counters": delta,
                    "trace": reduced,
                    "train_tok_s": (async_steps * tokens_per_step
                                    / (t_async - t0)),
                    "blocked_steps": blocked,
                    "blocked_s": t1 - t_async if blocked else None,
                    "params": n_params, "num_layers": config.num_layers,
                    "hidden_size": config.hidden_size,
                    "num_heads": config.num_heads,
                    "batch": int(mix["batch"]),
                    "seq_len": int(mix["seq_len"]),
                    "seconds": run.seconds}}
