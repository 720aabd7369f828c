"""GSPMD-sharded training THROUGH the Pallas flash kernel: a plain
(non-pipeline) Llama with Megatron-TP placements trains on the mesh
with attention routed to the Pallas path, and its loss curve matches
the single-device run (the integration the custom_partitioning rules
exist for — real-TPU GSPMD models keep the fused kernel)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import distributed as dist
from paddle_tpu import optimizer
from paddle_tpu.models import Llama, LlamaConfig

@pytest.fixture
def force_pallas(monkeypatch):
    # CPU backend routes to XLA sdpa by default; force the Pallas
    # (interpret-mode) kernel so the custom_partitioning path is what
    # actually executes under GSPMD
    monkeypatch.setenv("PADDLE_FLASH_FORCE", "pallas")


def _losses(mesh, steps=4):
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    if mesh is not None:
        dist.apply_placement_rules(
            model, Llama.tp_placement_rules(mesh), mesh)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, cfg.max_position_embeddings))
        .astype("int64"))
    if mesh is not None:
        step = dist.ShardedTrainStep(
            model, opt, lambda m, i: m.loss(i, i), mesh=mesh,
            data_placements=[dist.Shard(0), dist.Replicate()])
    else:
        step = paddle.jit.TrainStep(model, opt,
                                    lambda m, i: m.loss(i, i))
    return [float(np.asarray(step(ids).numpy())) for _ in range(steps)]


def test_tp_sharded_train_matches_single_device(force_pallas):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    ref = _losses(None)
    mesh = dist.init_mesh([2, 2], ["dp", "tp"])
    got = _losses(mesh)
    assert all(np.isfinite(got)), got
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)
    assert got[-1] < got[0]
