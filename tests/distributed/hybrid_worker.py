"""Worker for the 2-process x 4-device hybrid E2E test: a dp x mp train
step on a PROCESS-SPANNING mesh — the DCN-boundary analogue the
single-process 8-device dryrun cannot prove (reference
test/collective/test_communication_api_base.py:64 `--nnode`).

dp axis (2) crosses the process boundary (DCN analogue); mp axis (4) is
process-local (ICI analogue). Megatron-TP placements + ZeRO-sharded
optimizer state + dp-sharded data, one real train step, loss checked
finite and identical across processes.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import distributed as dist  # noqa: E402
from paddle_tpu import nn, optimizer  # noqa: E402
from paddle_tpu.models import Llama, LlamaConfig  # noqa: E402


def main():
    out_dir = sys.argv[1]
    dist.init_parallel_env()
    rank = dist.get_rank()
    assert dist.get_world_size() == 2
    assert jax.device_count() == 8, jax.devices()
    assert len(jax.local_devices()) == 4

    # dp spans the two processes; mp is local to each
    mesh = dist.init_mesh([2, 4], ["dp", "mp"])

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      max_position_embeddings=16)
    paddle.seed(7)  # same init on both processes
    model = Llama(cfg)
    dist.apply_placement_rules(model, Llama.tp_placement_rules(mesh, "mp"),
                               mesh)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters(),
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    step = dist.ShardedTrainStep(
        model, opt, lambda m, ids: m.loss(ids, ids), mesh=mesh,
        data_placements=[dist.Shard(0)], shard_optimizer_axis="dp")

    ids = np.random.default_rng(5).integers(0, cfg.vocab_size,
                                            (8, 16)).astype("int64")
    losses = [float(step(paddle.to_tensor(ids))) for _ in range(2)]
    assert all(np.isfinite(losses)), losses
    assert losses[1] < losses[0] + 1.0  # step applied, nothing exploded

    with open(os.path.join(out_dir, f"hybrid_loss.{rank}"), "w") as f:
        f.write(repr(losses))
    print(f"rank {rank} hybrid dp2(x-process) x mp4 losses {losses}",
          flush=True)


if __name__ == "__main__":
    main()
