"""Pipeline-parallel engine tests (GPipe ppermute loop under shard_map).

Acc-align strategy per SURVEY.md §4: dist loss curve pinned to the
single-device curve.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu import distributed as dist
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


def _loss_fn(logits, labels):
    return F.cross_entropy(logits[:, :-1, :], labels[:, 1:])


def _make_pipe(mesh, n_micro=4):
    paddle.seed(21)
    cfg = LlamaConfig.tiny()
    model = Llama(cfg)
    pipe = PipelineDecoderLM(model.embed_tokens, model.layers,
                             Head(model.norm, model.lm_head), _loss_fn,
                             mesh, pp_axis="pp", num_microbatches=n_micro)
    return model, pipe


@pytest.fixture(scope="module")
def ids_np():
    return np.random.default_rng(5).integers(0, 255, (8, 32)).astype(
        "int64")


def test_pipeline_loss_matches_single(ids_np):
    mesh = dist.init_mesh([2, 2, 2], ["dp", "pp", "tp"])
    model, pipe = _make_pipe(mesh)
    ids = paddle.to_tensor(ids_np)
    ref = float(model.loss(ids, ids))
    got = float(pipe.loss(ids, ids))
    np.testing.assert_allclose(got, ref, rtol=1e-5)


def test_pipeline_grads_match_single(ids_np):
    mesh = dist.init_mesh([1, 2, 1], ["dp", "pp", "tp"])
    model, pipe = _make_pipe(mesh)
    ids = paddle.to_tensor(ids_np)

    # single-device grads on a fresh identical model
    paddle.seed(21)
    ref = Llama(LlamaConfig.tiny())
    ref.loss(ids, ids).backward()
    ref_block0 = dict(ref.layers[0].named_parameters())

    pipe.loss(ids, ids).backward()
    stacked = {p.name: p for p in pipe.stacked_parameters()}
    for name, rp in ref_block0.items():
        sp = stacked["blocks." + name]
        np.testing.assert_allclose(
            sp.grad.numpy()[0], rp.grad.numpy(), rtol=2e-3, atol=2e-4)


def test_pipeline_train_loop_acc_align(ids_np):
    """dp2 x pp2 x tp2 hybrid training == single-device training."""
    ids = paddle.to_tensor(ids_np)

    paddle.seed(21)
    single = Llama(LlamaConfig.tiny())
    opt_s = optimizer.AdamW(learning_rate=1e-3,
                            parameters=single.parameters())
    step_s = paddle.jit.TrainStep(single, opt_s,
                                  lambda m, i: m.loss(i, i))
    ref_losses = [float(step_s(ids)) for _ in range(3)]

    mesh = dist.init_mesh([2, 2, 2], ["dp", "pp", "tp"])
    _, pipe = _make_pipe(mesh)
    opt_p = optimizer.AdamW(learning_rate=1e-3,
                            parameters=pipe.parameters())
    step_p = dist.ShardedTrainStep(
        pipe, opt_p, lambda m, i: m.loss(i, i), mesh=mesh,
        data_placements=[dist.Shard(0), dist.Replicate(),
                         dist.Replicate()])
    pipe_losses = [float(step_p(ids)) for _ in range(3)]
    np.testing.assert_allclose(pipe_losses, ref_losses, rtol=1e-3,
                               atol=1e-3)


def test_pipeline_microbatch_counts(ids_np):
    mesh = dist.init_mesh([1, 2, 1], ["dp", "pp", "tp"])
    ids = paddle.to_tensor(ids_np)
    losses = []
    for m in (2, 4, 8):
        model, pipe = _make_pipe(mesh, n_micro=m)
        losses.append(float(pipe.loss(ids, ids)))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
    np.testing.assert_allclose(losses[0], losses[2], rtol=1e-5)
