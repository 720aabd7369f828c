"""Capture a jax.profiler device trace of the headline train step on
the real TPU (3 steps after warmup) into traces/headline_tpu/.

The XPlane protobuf under traces/headline_tpu/plugins/profile/... is
the hardware evidence of where the 345M step's time goes (MXU vs
memory-bound fusions vs the Pallas flash calls) — the CUPTI-timeline
equivalent for the TPU (SURVEY §5.1). Run on the chip, from the root of
the checkout, as the only process that touches JAX:

    python tools/capture_headline_trace.py [--steps 3] [--out DIR]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", default="traces/headline_tpu")
    args = ap.parse_args()

    import jax

    from paddle_tpu.utils import configure_compile_cache

    configure_compile_cache()
    if jax.default_backend() == "cpu":
        print(json.dumps({"skipped": "CPU backend — trace must be "
                                     "captured on the TPU"}))
        return 1

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.models import GPT, GPTConfig

    cfg = GPTConfig.gpt2_medium()
    paddle.seed(0)
    model = GPT(cfg)
    model.to(dtype="bfloat16")
    opt = optimizer.AdamW(learning_rate=3e-4,
                          parameters=model.parameters(),
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    step = paddle.jit.TrainStep(model, opt, lambda m, ids: m.loss(ids, ids))
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 1024)).astype("int64"))
    float(step(ids).numpy())  # compile + warm
    float(step(ids).numpy())

    os.makedirs(args.out, exist_ok=True)
    jax.profiler.start_trace(args.out)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss = step(ids)
    lv = float(loss.numpy())
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()

    files = []
    for root, _dirs, fnames in os.walk(args.out):
        files += [os.path.join(root, f) for f in fnames]
    print(json.dumps({
        "steps": args.steps, "step_time_ms": round(dt / args.steps * 1e3, 2),
        "loss": lv, "trace_files": len(files),
        "device": getattr(jax.devices()[0], "device_kind", "?"),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
