"""FP8 GPT pretraining example: bf16 vs fp8 loss curves side by side.

Run:  python examples/fp8_gpt.py  (CPU mesh or a TPU chip)

The fp8 path quantizes every transformer-block linear to e4m3
activations/weights with e5m2 gradients under a delayed-scaling recipe
(paddle.amp.fp8); the LM head stays bf16. The whole step — including
the amax-history updates — compiles into one donated XLA executable.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.models import GPT, GPTConfig


def run(use_fp8, steps=30):
    paddle.seed(0)
    cfg = GPTConfig.tiny()
    cfg.use_fp8 = use_fp8
    model = GPT(cfg)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters(),
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    step = paddle.jit.TrainStep(model, opt,
                                lambda m, ids: m.loss(ids, ids))
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 64)).astype("int64"))
    losses = [float(np.asarray(step(ids).numpy())) for _ in range(steps)]
    return losses


if __name__ == "__main__":
    bf16 = run(False)
    fp8 = run(True)
    print(f"{'step':>4}  {'bf16':>8}  {'fp8':>8}")
    for i in range(0, len(bf16), 5):
        print(f"{i:>4}  {bf16[i]:>8.4f}  {fp8[i]:>8.4f}")
    dev = max(abs(a - b) / max(abs(b), 1e-6) for a, b in zip(fp8, bf16))
    print(f"max relative deviation fp8 vs bf16: {dev:.3f}")
