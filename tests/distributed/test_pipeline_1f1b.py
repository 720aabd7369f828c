"""1F1B / interleaved (VPP) / FThenB pipeline schedules.

Parity: reference pipeline_parallel.py:545 (1F1B), :1136 (interleave),
:1957 (FThenB); pp_layers.py LayerDesc/SharedLayerDesc. Acc-align: every
schedule must produce the same loss/grads as the GPipe engine; the
scheduler's stash depth must stay ~P (not M) for 1F1B — that buffer IS
the engine's activation residency.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu import distributed as dist
from paddle_tpu.distributed.pipeline import (LayerDesc, PipelineDecoderLM,
                                             SharedLayerDesc)
from paddle_tpu.distributed.pipeline_schedule import build_schedule
from paddle_tpu.models import Llama, LlamaConfig
from paddle_tpu.nn import functional as F

CFG = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                  num_layers=4, num_heads=4, num_kv_heads=2,
                  max_position_embeddings=16)


class _Head(nn.Layer):
    def __init__(self, norm, lm_head):
        super().__init__()
        self.norm = norm
        self.lm_head = lm_head

    def forward(self, x):
        return self.lm_head(self.norm(x))


def _loss_fn(logits, labels):
    return F.cross_entropy(logits[:, :-1, :], labels[:, 1:])


def _make(mesh, schedule, M, V=1, cfg=CFG):
    paddle.seed(0)
    m = Llama(cfg)
    return PipelineDecoderLM(
        m.embed_tokens, m.layers, _Head(m.norm, m.lm_head), _loss_fn,
        mesh, pp_axis="pp", num_microbatches=M, schedule=schedule,
        num_virtual_stages=V)


def _ids(cfg=CFG, batch=8):
    return paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, cfg.max_position_embeddings)
    ).astype("int64"))


# ---------------------------------------------------------------- scheduler

def test_schedule_dependencies_respected():
    for style, V in [("fthenb", 1), ("1f1b", 1), ("interleave", 2),
                     ("1f1b_packed", 1), ("interleave_packed", 2),
                     ("zb", 1)]:
        s = build_schedule(4, V, 8, style)
        N = 4 * V
        fdone, bdone = {}, {}
        for t in range(s.T):
            for d in range(4):
                c, f = int(s.fchunk[d, t]), int(s.fmb[d, t])
                if c >= 0:
                    g = c * 4 + d
                    if g > 0:
                        assert fdone[(g - 1, f)] < t, (style, g, f)
                    fdone[(g, f)] = t
                c, b = int(s.bchunk[d, t]), int(s.bmb[d, t])
                if c >= 0:
                    g = c * 4 + d
                    if g == N - 1:
                        assert fdone[(g, b)] < t
                    else:
                        assert bdone[(g + 1, b)] < t, (style, g, b)
                    bdone[(g, b)] = t
        assert len(fdone) == len(bdone) == N * 8


def test_1f1b_stash_depth_is_P_not_M():
    """The 1F1B memory claim: in-flight activations stay ~P as M grows
    (GPipe/FThenB grows linearly with M)."""
    P = 4
    depths = [build_schedule(P, 1, M, "1f1b").stash_depth
              for M in (4, 16, 64)]
    assert depths[0] == depths[1] == depths[2] == P
    assert build_schedule(P, 1, 64, "fthenb").stash_depth == 64
    # interleave: bounded by warmup cap, independent of M
    v1 = build_schedule(P, 2, 8, "interleave").stash_depth
    v2 = build_schedule(P, 2, 32, "interleave").stash_depth
    assert v1 == v2 < 32


def test_1f1b_bubble_smaller_than_fthenb_span():
    sf = build_schedule(4, 1, 16, "fthenb")
    s1 = build_schedule(4, 1, 16, "1f1b")
    assert s1.T <= sf.T  # same or tighter makespan


# ---------------------------------------------------------------- acc-align

@pytest.fixture(scope="module")
def gpipe_ref():
    mesh = dist.init_mesh([2, 4], ["dp", "pp"])
    pg = _make(mesh, "gpipe", 4)
    ids = _ids()
    loss = pg.loss(ids, ids)
    loss.backward()
    return {
        "mesh": mesh,
        "ids": ids,
        "loss": float(np.asarray(loss._data)),
        "block_grads": {p.name: np.asarray(p.grad._data)
                        for p in pg.stacked_parameters()},
        "embed_grads": {n: np.asarray(p.grad._data)
                        for n, p in pg.embed.named_parameters()},
        "head_grads": {n: np.asarray(p.grad._data)
                       for n, p in pg.head.named_parameters()},
    }


def _check_align(pipe, ref, layers=4):
    ids = ref["ids"]
    loss = pipe.loss(ids, ids)
    loss.backward()
    np.testing.assert_allclose(float(np.asarray(loss._data)), ref["loss"],
                               rtol=1e-5)
    # stacked grads come back in ORIGINAL layer order regardless of the
    # engine's internal (P, V) row permutation
    for p in pipe.stacked_parameters():
        got = np.asarray(p.grad._data)
        want = ref["block_grads"][p.name]
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5,
                                   err_msg=p.name)
    for n, p in pipe.embed.named_parameters():
        np.testing.assert_allclose(np.asarray(p.grad._data),
                                   ref["embed_grads"][n],
                                   rtol=3e-4, atol=3e-5, err_msg=n)
    for n, p in pipe.head.named_parameters():
        np.testing.assert_allclose(np.asarray(p.grad._data),
                                   ref["head_grads"][n],
                                   rtol=3e-4, atol=3e-5, err_msg=n)


def test_1f1b_acc_align(gpipe_ref):
    _check_align(_make(gpipe_ref["mesh"], "1f1b", 4), gpipe_ref)


def test_fthenb_acc_align(gpipe_ref):
    _check_align(_make(gpipe_ref["mesh"], "fthenb", 4), gpipe_ref)


def test_interleave_acc_align_with_padding(gpipe_ref):
    """V=2 over pp=4 -> 8 virtual stages from 4 real layers: exercises
    identity-masked pad rows + round-robin chunk placement."""
    _check_align(_make(gpipe_ref["mesh"], "interleave", 8, V=2), gpipe_ref)


def test_1f1b_packed_acc_align(gpipe_ref):
    """Packed: a device may fire F and B in the same tick."""
    _check_align(_make(gpipe_ref["mesh"], "1f1b_packed", 4), gpipe_ref)


def test_zb_acc_align(gpipe_ref):
    """ZB-H1: backward split into activation-grad (B) and deferred
    weight-grad (W) ops — gradients must still match GPipe exactly."""
    _check_align(_make(gpipe_ref["mesh"], "zb", 4), gpipe_ref)


def test_zb_w_after_b_and_memory_capped():
    s = build_schedule(4, 1, 16, "zb")
    for d in range(4):
        bt = {int(m): t for t, m in enumerate(s.bmb[d]) if m >= 0}
        wt = {int(m): t for t, m in enumerate(s.wmb[d]) if m >= 0}
        assert set(bt) == set(wt) == set(range(16))
        for m in range(16):
            assert wt[m] > bt[m]
    # ZB-H1 memory bound: stash stays ~P as M grows (not M)
    assert build_schedule(4, 1, 64, "zb").stash_depth <= 4 + 1


# ----------------------------------------------------------- train step

def test_1f1b_under_sharded_train_step(gpipe_ref):
    mesh = gpipe_ref["mesh"]

    def run(schedule, V=1):
        pipe = _make(mesh, schedule, 4, V=V)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=pipe.parameters(),
                              grad_clip=nn.ClipGradByGlobalNorm(1.0))
        step = dist.ShardedTrainStep(
            pipe, opt, lambda m, ids: m.loss(ids, ids), mesh=mesh,
            data_placements=[dist.Shard(0), dist.Replicate()],
            shard_optimizer_axis="dp")
        return [float(np.asarray(step(gpipe_ref["ids"])._data))
                for _ in range(3)]

    l_g = run("gpipe")
    l_1 = run("1f1b")
    np.testing.assert_allclose(l_1, l_g, rtol=2e-4)
    assert l_1[-1] < l_1[0]  # training moves


# ----------------------------------------------------------- descriptors

def test_shared_layer_desc_ties_embedding():
    mesh = dist.init_mesh([1, 4], ["dp", "pp"])
    cfg = CFG

    class Embed(nn.Layer):
        def __init__(self):
            super().__init__()
            self.weight = self.create_parameter(
                [cfg.vocab_size, cfg.hidden_size], dtype="float32")

        def forward(self, ids):
            return F.embedding(ids, self.weight)

    class TiedHead(nn.Layer):
        def __init__(self):
            super().__init__()
            self.weight = self.create_parameter(
                [cfg.vocab_size, cfg.hidden_size], dtype="float32")

        def forward(self, x):
            return paddle.matmul(x, self.weight.T)

    paddle.seed(0)
    blocks = [LayerDesc(nn.Linear, cfg.hidden_size, cfg.hidden_size)
              for _ in range(4)]
    pipe = PipelineDecoderLM.from_descs(
        [SharedLayerDesc("emb", Embed),
         *blocks,
         SharedLayerDesc("emb", TiedHead)],
        _loss_fn, mesh, num_microbatches=4, schedule="1f1b")
    # one Parameter object, two positions
    assert pipe.embed.weight is pipe.head.weight
    ids = _ids()
    loss = pipe.loss(ids, ids)
    loss.backward()
    g_tied = np.asarray(pipe.embed.weight.grad._data)
    assert np.isfinite(g_tied).all() and np.abs(g_tied).sum() > 0

    # tied grad == embed-position grad + head-position grad (untied run)
    paddle.seed(0)
    pipe2 = PipelineDecoderLM.from_descs(
        [SharedLayerDesc("emb", Embed),
         *[LayerDesc(nn.Linear, cfg.hidden_size, cfg.hidden_size)
           for _ in range(4)],
         SharedLayerDesc("emb2", TiedHead)],
        _loss_fn, mesh, num_microbatches=4, schedule="1f1b")
    assert pipe2.embed.weight is not pipe2.head.weight
    pipe2.head.weight._rebind(pipe2.embed.weight._data)  # same values
    loss2 = pipe2.loss(ids, ids)
    loss2.backward()
    g_sum = (np.asarray(pipe2.embed.weight.grad._data) +
             np.asarray(pipe2.head.weight.grad._data))
    np.testing.assert_allclose(g_tied, g_sum, rtol=2e-4, atol=2e-5)


def test_uneven_layers_padded():
    """6 layers over pp=4: pads to 8 rows, identity-masked (reference
    SegmentLayers uneven partition capability)."""
    mesh = dist.init_mesh([1, 4], ["dp", "pp"])
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_layers=6, num_heads=4, num_kv_heads=2,
                      max_position_embeddings=16)
    paddle.seed(0)
    m = Llama(cfg)
    pipe = PipelineDecoderLM(
        m.embed_tokens, m.layers, _Head(m.norm, m.lm_head), _loss_fn,
        mesh, num_microbatches=4, schedule="1f1b")
    assert pipe._n_layers_padded == 8

    # oracle: plain (non-pipeline) forward on the same weights
    paddle.seed(0)
    m2 = Llama(cfg)
    ids = _ids(cfg)
    logits = m2(ids)
    want = float(np.asarray(_loss_fn(logits, ids)._data))
    got = float(np.asarray(pipe.loss(ids, ids)._data))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_state_dict_schedule_independent():
    """A checkpoint saved under interleave loads into a V=1 pipeline with
    identical per-layer values (stacked params stored in original layer
    order, engine permutation internal)."""
    mesh = dist.init_mesh([1, 4], ["dp", "pp"])
    pv = _make(mesh, "interleave", 8, V=2)
    p1 = _make(mesh, "1f1b", 4)
    for a, b in zip(pv.stacked_parameters(), p1.stacked_parameters()):
        assert tuple(a.shape) == tuple(b.shape)  # [L, ...], no padding
        np.testing.assert_allclose(np.asarray(a._data),
                                   np.asarray(b._data))  # same seed
    sd = pv.state_dict()
    p1.set_state_dict(sd)
    ids = _ids()
    lv = float(np.asarray(pv.loss(ids, ids)._data))
    l1 = float(np.asarray(p1.loss(ids, ids)._data))
    np.testing.assert_allclose(lv, l1, rtol=1e-5)


def test_shared_layer_desc_forward_func():
    """forward_func replaces the layer's forward at that pipeline
    position (reference SharedLayerDesc usage: tied embedding as head)."""
    mesh = dist.init_mesh([1, 4], ["dp", "pp"])
    cfg = CFG

    class Embed(nn.Layer):
        def __init__(self):
            super().__init__()
            self.weight = self.create_parameter(
                [cfg.vocab_size, cfg.hidden_size], dtype="float32")

        def forward(self, ids):
            return F.embedding(ids, self.weight)

    def as_head(self, x):
        return paddle.matmul(x, self.weight.T)

    paddle.seed(0)
    pipe = PipelineDecoderLM.from_descs(
        [SharedLayerDesc("emb", Embed),
         *[LayerDesc(nn.Linear, cfg.hidden_size, cfg.hidden_size)
           for _ in range(4)],
         SharedLayerDesc("emb", Embed, forward_func=as_head)],
        _loss_fn, mesh, num_microbatches=4, schedule="1f1b")
    assert pipe.embed.weight is pipe.head.weight
    ids = _ids()
    loss = pipe.loss(ids, ids)
    assert np.isfinite(float(np.asarray(loss._data)))


def test_schedule_cost_report_measured_costs():
    """costs= plugs hardware-measured per-phase times into the tick
    table (tools/pipeline_tick_ab.py feeds TPU numbers through this)."""
    from paddle_tpu.distributed.pipeline_schedule import (
        schedule_cost_report)

    analytic = schedule_cost_report(4, 8)
    # same relative structure when every cost is scaled by a constant
    scaled = schedule_cost_report(
        4, 8, costs={"F": 2.0, "B": 6.0, "Bd": 4.0, "W": 4.0})
    for style in analytic:
        assert scaled[style]["ticks"] == analytic[style]["ticks"]
        assert scaled[style]["lockstep_cost"] == \
            2 * analytic[style]["lockstep_cost"]
    # a partial override keeps defaults for the rest
    part = schedule_cost_report(4, 8, costs={"B": 3.0})
    assert part["1f1b"]["lockstep_cost"] == \
        analytic["1f1b"]["lockstep_cost"]
    # measured regime where W is nearly free: zb must BEAT 1f1b in the
    # model — the report reflects the costs, not a baked-in stance
    free_w = schedule_cost_report(
        8, 32, costs={"F": 1.0, "B": 3.0, "Bd": 2.0, "W": 0.01})
    assert free_w["zb"]["lockstep_cost"] < \
        free_w["1f1b"]["lockstep_cost"]
