"""GPT-2 pretraining end-to-end: native mmap data pipeline + compiled
train step + checkpoint/resume + profiler.

Usage:
  python examples/pretrain_gpt.py --tokens tokens.bin --steps 100
  (without --tokens, synthesizes random data)
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.models import GPT, GPTConfig


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tokens", default=None,
                   help=".bin file of uint16 token ids")
    p.add_argument("--model", default="tiny",
                   choices=["tiny", "small", "medium"])
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=10)
    p.add_argument("--bf16", action="store_true")
    p.add_argument("--save", default=None)
    p.add_argument("--resume", default=None)
    args = p.parse_args()

    paddle.seed(0)
    config = {"tiny": GPTConfig.tiny, "small": GPTConfig.gpt2_small,
              "medium": GPTConfig.gpt2_medium}[args.model]()
    args.seq = min(args.seq, config.max_position_embeddings)
    model = GPT(config)
    if args.bf16:
        model.to(dtype="bfloat16")

    sched = optimizer.lr.LinearWarmup(
        optimizer.lr.CosineAnnealingDecay(args.lr, T_max=args.steps),
        warmup_steps=args.warmup, start_lr=0.0, end_lr=args.lr)
    opt = optimizer.AdamW(learning_rate=sched, weight_decay=0.1,
                          parameters=model.parameters(),
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    step = paddle.jit.TrainStep(model, opt,
                                lambda m, x, y: m.loss(x, y))

    if args.resume:
        state = paddle.load(args.resume)
        model.set_state_dict(state["model"])
        opt.set_state_dict(state["opt"])
        print(f"resumed from {args.resume}")

    if args.tokens:
        from paddle_tpu.io.token_dataset import MMapTokenDataset
        ds = MMapTokenDataset(args.tokens, args.batch, args.seq,
                              dtype="uint16", seed=0)
        def batches():
            while True:
                yield from ds
    else:
        rng = np.random.default_rng(0)
        def batches():
            while True:
                ids = rng.integers(0, config.vocab_size,
                                   (args.batch, args.seq + 1))
                yield (paddle.to_tensor(ids[:, :-1].astype("int64")),
                       paddle.to_tensor(ids[:, 1:].astype("int64")))

    it = iter(batches())
    t0 = time.time()
    for i in range(args.steps):
        x, y = next(it)
        loss = step(x, y)
        sched.step()
        if i % 10 == 0 or i == args.steps - 1:
            val = float(np.asarray(loss._data))
            toks = args.batch * args.seq * (i + 1) / (time.time() - t0)
            print(f"step {i:5d}  loss {val:.4f}  lr {opt.get_lr():.2e}  "
                  f"{toks:,.0f} tok/s")

    if args.save:
        paddle.save({"model": model.state_dict(),
                     "opt": opt.state_dict()}, args.save)
        print(f"saved to {args.save}")


if __name__ == "__main__":
    main()
