"""Hybrid-parallel Llama pretraining: dp x pp x tp mesh with Megatron-TP
placements, pipeline microbatching, sequence-sharded activations, and
ZeRO-sharded optimizer state.

Runs on real chips or a virtual CPU mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/hybrid_llama.py --mesh 2,2,2
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import time

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import distributed as dist
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed.pipeline import PipelineDecoderLM
from paddle_tpu.models import Llama, LlamaConfig
from paddle_tpu.nn import functional as F


class Head(nn.Layer):
    def __init__(self, norm, lm_head):
        super().__init__()
        self.norm = norm
        self.lm_head = lm_head

    def forward(self, x):
        return self.lm_head(self.norm(x))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--mesh", default="2,2,2",
                   help="dp,pp,tp degrees (product = device count)")
    p.add_argument("--micro", type=int, default=4)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=20)
    args = p.parse_args()

    dp, pp, tp = (int(x) for x in args.mesh.split(","))
    paddle.seed(0)
    mesh = dist.init_mesh([dp, pp, tp], ["dp", "pp", "tp"])
    config = LlamaConfig.tiny()
    model = Llama(config)
    dist.apply_placement_rules(model, Llama.tp_placement_rules(mesh), mesh)

    pipe = PipelineDecoderLM(
        model.embed_tokens, model.layers,
        Head(model.norm, model.lm_head),
        lambda logits, labels: F.cross_entropy(logits[:, :-1, :],
                                               labels[:, 1:]),
        mesh, pp_axis="pp", num_microbatches=args.micro)

    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=pipe.parameters(),
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    step = dist.ShardedTrainStep(
        pipe, opt, lambda m, ids: m.loss(ids, ids), mesh=mesh,
        data_placements=[dist.Shard(0), dist.Replicate(), dist.Shard(1)],
        shard_optimizer_axis="dp" if dp > 1 else None)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(
        0, config.vocab_size,
        (args.batch, config.max_position_embeddings)).astype("int64"))
    t0 = time.time()
    for i in range(args.steps):
        loss = step(ids)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(np.asarray(loss._data)):.4f}")
    print(f"{args.steps} steps in {time.time() - t0:.1f}s on mesh "
          f"dp{dp} x pp{pp} x tp{tp}")


if __name__ == "__main__":
    main()
