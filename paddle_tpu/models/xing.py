"""Xing4.0 (``model_type`` ``xing4_0``): latent attention (MLA), sparse
experts routed by sigmoid scores beside a shared expert, and a residual
of several streams mixed by manifold-constrained hyper-connections.

**Residual streams** (``n = hc_mult`` streams of ``d``). ``X_0[t]`` is
the embedding repeated ``n`` times. Each sublayer ``F`` (attention, then
the feed-forward part, each behind its own RMSNorm) has maps of its own,
all float32 from ``z`` to ``M``::

    z = RMSNorm(vec(X))                        over the n*d values
    Hpre  = sigmoid(a_pre  * (z @ phi_pre ) + b_pre )          [n]
    Hpost = 2 sigmoid(a_post * (z @ phi_post) + b_post)        [n]
    M = exp(clip(a_res * mat(z @ phi_res) + b_res, lo, hi))    [n, n]
    hc_sinkhorn_iters times:  M /= colsum(M) + eps;  M /= rowsum(M) + eps
    u = Hpre @ X;   X = M @ X + outer(Hpost, F(u))

and after the last layer ``h = sum of the streams``, the final norm, the
untied head.

**Latent attention.** For a token's input ``u`` at position ``p``::

    c_q = RMSNorm(u W_dq);  [q_nope, q_rope]_h = split(c_q W_uq);  q_rope <- rotary(p)
    [c, k_r] = split(u W_dkv);  c = RMSNorm(c);  k_r <- rotary(p)      the cached row
    [k_nope, v]_h = split(c W_ukv)
    score_h(p, s) = scale * (q_nope_h(p) . k_nope_h(s) + q_rope_h(p) . k_r(s))
    out = concat_h(softmax_s(score_h) @ v_h) W_o

with YaRN frequencies and ``scale = (nope + rope)^-0.5 * (0.1
mscale_all_dim ln(factor) + 1)^2``. That is the **expanded** form: the
whole-sequence ``forward`` and the prefill programs run it. The decode
program runs the **absorbed** form, the same mathematics with ``W_ukv``
folded into the query and the output, so that the cache is read as it
lies (``kernels/pallas/mla_decode.py``)::

    q_lat_h = q_nope_h W_uk_h^T;  score_h = scale * (q_lat_h . c(s) + q_rope_h . k_r(s))
    o_lat_h = softmax(score_h) @ c;  out_h = o_lat_h W_uv_h

**Feed-forward part.** The first ``first_k_dense_replace`` layers are a
dense SwiGLU; the others ``distributed.moe.DroplessMoE`` with sigmoid
scores, a correction bias that picks and never weighs, the chosen
scores renormalised and scaled, and one shared expert every token
passes.

Served through ``ServingEngine`` on ``PagedServingModel``: the model
declares ``latent_rows`` (the cache then holds one row a token a layer,
``inference.paged.LatentRowSpec``) and owns how a sublayer reads and
writes the residual (``residual_read`` / ``residual_write``); the shared
stack, the call protocol, the run-ahead loop and the prefix cache are
everybody's. ``docs/SERVING.md`` "A latent cache" says what is refused
with such a cache, and why. Matrices are ``[in, out]``. The multi-token
prediction module of the family is a draft head and no part of this
forward: ``num_nextn_predict_layers`` must be 0.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.random import next_key
from ..core.tensor import Tensor
from ..distributed.moe import DroplessMoE
from ..inference.paged import LatentRowSpec
from ..kernels.pallas.mla_decode import mla_decode_routed, in_lanes
from ..nn.initializer import Constant, Initializer
from ..profiler.tracing import phase as _phase
from ..profiler.tracing import scope as _scope
from .llama import LlamaMLP, PagedServingModel, _normal_attr

__all__ = ["Xing", "XingConfig", "yarn_inv_freq", "sinkhorn"]

_F32 = jnp.float32


@dataclasses.dataclass
class XingConfig:
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_layers: int = 40
    num_heads: int = 32
    num_kv_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 2
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rope_theta: float = 10000.0
    # YaRN, the family's ``rope_scaling`` group
    rope_scaling: dict = None
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    num_nextn_predict_layers: int = 0
    # experts held here, (lo, hi); None = all
    expert_range: tuple = None

    def __post_init__(self):
        if self.rope_scaling is None:
            self.rope_scaling = {
                "type": "yarn", "factor": 64, "beta_fast": 32,
                "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                "original_max_position_embeddings": 4096}
        refused = [
            (self.num_nextn_predict_layers, "num_nextn_predict_layers > 0 "
             "(the multi-token prediction module is a draft head, no part "
             "of the main model's forward; serving/spec.py cannot draft "
             "with it yet)"),
            (self.n_group != 1 or self.topk_group != 1, "n_group / "
             "topk_group other than 1 (a group-limited choice of experts)"),
            (self.scoring_func != "sigmoid" or self.topk_method
             != "noaux_tc", "a router other than noaux_tc over sigmoid "
             "scores"),
            (self.n_shared_experts != 1, "other than one shared expert"),
            (self.rope_scaling.get("type") != "yarn", "rope_scaling other "
             "than yarn")]
        for bad, what in refused:
            if bad:
                raise ValueError(f"XingConfig: {what} is not modelled.")

    @property
    def head_dim(self):
        """A query's (and an expanded key's) size a head."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        rs = self.rope_scaling
        mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0 \
            if rs["factor"] > 1 else 1.0
        return self.head_dim ** -0.5 * mscale * mscale

    @staticmethod
    def xing4_29b_a4b():
        return XingConfig()

    @staticmethod
    def tiny():
        """1 dense + 2 sparse layers, 4 heads of 16 + 8 over a latent of
        32, 8 experts of 24 (2 a token) beside a shared one, 4 streams
        of 32."""
        return XingConfig(
            vocab_size=256, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24, num_layers=3, num_heads=4,
            num_kv_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=8, num_experts_per_tok=2,
            first_k_dense_replace=1, max_position_embeddings=4096,
            rope_scaling={"type": "yarn", "factor": 8, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 64})


def yarn_inv_freq(dim, theta, rs):
    """The family's YaRN frequencies [dim / 2] (numpy float64 -> float32):
    ``theta^(-2i/dim)`` blended with the same over ``factor`` by a linear
    ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times over the original context."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / theta ** (i / dim)
    orig = rs["original_max_position_embeddings"]

    def turns_at(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(rs["beta_fast"])), 0)
    high = min(math.ceil(turns_at(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / rs["factor"] * ramp + extra * (1.0 - ramp)).astype(
        np.float32)


def _rotary(x, positions, inv_freq, magnitude):
    """Neighbouring pairs of ``x`` [b, s, ..., dim] rotated to
    ``positions`` [b, s] (float32 inside)."""
    freqs = positions.astype(_F32)[..., None] * inv_freq   # [b, s, dim/2]
    shape = freqs.shape[:2] + (1,) * (x.ndim - 3) + freqs.shape[2:]
    cos = (jnp.cos(freqs) * magnitude).reshape(shape)
    sin = (jnp.sin(freqs) * magnitude).reshape(shape)
    x1, x2 = x[..., 0::2].astype(_F32), x[..., 1::2].astype(_F32)
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def sinkhorn(m, iters, eps):
    """``iters`` rounds of columns-then-rows normalisation of the
    positive ``m`` [.., n, n]: doubly stochastic in the limit."""
    for _ in range(int(iters)):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


class _ResBiasInit(Initializer):
    """The maps' biases [n (pre), n (post), n*n (res)]: zeros, but the
    residual map's diagonal ``diag``: the mix starts near the identity."""

    def __init__(self, n, diag):
        self.n, self.diag = n, diag

    def __call__(self, shape, dtype):
        n = self.n
        return jnp.concatenate([
            jnp.zeros((2 * n,), _F32),
            (self.diag * jnp.eye(n, dtype=_F32)).reshape(-1)]).astype(dtype)


class XingHyperConnection(nn.Layer):
    """One sublayer's maps over the ``n`` residual streams."""

    # the token's part of each map starts at this weight (the config has
    # no key for it): far enough from zero that it moves the logits
    ALPHA = 0.25
    RES_DIAGONAL = 2.0

    def __init__(self, config: XingConfig):
        super().__init__()
        n, d = config.hc_mult, config.hidden_size
        self.n = n
        self.iters, self.eps = config.hc_sinkhorn_iters, config.hc_eps
        self.norm_eps = config.rms_norm_eps
        self.clamp = (config.mhc_h_res_clamp_min,
                      config.mhc_h_res_clamp_max)
        self.norm_weight = self.create_parameter(
            [n * d], default_initializer=Constant(1.0))
        self.phi = self.create_parameter(
            [n * d, n * (n + 2)],
            attr=_normal_attr(config.initializer_range))
        self.alpha = self.create_parameter(
            [3], default_initializer=Constant(self.ALPHA))
        self.bias = self.create_parameter(
            [n * (n + 2)],
            default_initializer=_ResBiasInit(n, self.RES_DIAGONAL))

    def maps(self, x, iters=None):
        """(Hpre [.., n], Hpost [.., n], M [.., n, n]) float32 of the
        streams ``x`` [.., n, d] (an array)."""
        n = self.n
        zf = x.astype(_F32).reshape(x.shape[:-2] + (-1,))
        z = zf * jax.lax.rsqrt(jnp.mean(zf * zf, -1, keepdims=True)
                               + self.norm_eps) \
            * self.norm_weight._data.astype(_F32)
        proj = jnp.matmul(z, self.phi._data.astype(_F32),
                          precision=jax.lax.Precision.HIGHEST)
        a, b = self.alpha._data.astype(_F32), self.bias._data.astype(_F32)
        pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
        res = a[2] * proj[..., 2 * n:] + b[2 * n:]
        m = jnp.exp(jnp.clip(res, *self.clamp)).reshape(
            res.shape[:-1] + (n, n))
        return pre, post, sinkhorn(
            m, self.iters if iters is None else iters, self.eps)


class XingAttention(nn.Layer):
    def __init__(self, config: XingConfig):
        super().__init__()
        d, h = config.hidden_size, config.num_heads
        self.num_heads = h
        self.nope, self.rope = (config.qk_nope_head_dim,
                                config.qk_rope_head_dim)
        self.v_dim, self.latent = config.v_head_dim, config.kv_lora_rank
        self.lanes = LatentRowSpec(self.latent, self.rope).lanes
        self.scale = config.softmax_scale
        rs = config.rope_scaling
        self.inv_freq = yarn_inv_freq(self.rope, config.rope_theta, rs)
        # the family's cos/sin magnitude: mscale over mscale_all_dim
        self.magnitude = np.float32(
            (0.1 * rs["mscale"] * math.log(rs["factor"]) + 1.0)
            / (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0))
        attr = _normal_attr(config.initializer_range)
        eps = config.rms_norm_eps

        def linear(i, o):
            return nn.Linear(i, o, weight_attr=attr, bias_attr=False)

        self.q_a_proj = linear(d, config.q_lora_rank)
        self.q_a_layernorm = nn.RMSNorm(config.q_lora_rank, epsilon=eps)
        self.q_b_proj = linear(config.q_lora_rank,
                               h * (self.nope + self.rope))
        self.kv_a_proj_with_mqa = linear(d, self.latent + self.rope)
        self.kv_a_layernorm = nn.RMSNorm(self.latent, epsilon=eps)
        self.kv_b_proj = linear(self.latent, h * (self.nope + self.v_dim))
        self.o_proj = linear(h * self.v_dim, d)

    def qkv(self, u, position_offset=0):
        """Of the normed input ``u`` [b, s, d] at the positions from
        ``position_offset`` (a scalar, or one offset a row of the
        batch): the queries [b, s, H, nope + rope] (the rotary part
        rotated) and the row the cache holds, in its pool's shape [b, s,
        1, lanes]: ``c`` [latent], the rotary keys [rope], zeros to the
        tile's end."""
        b, s, _ = u.shape
        off = jnp.asarray(position_offset, jnp.int32)
        pos = (off[:, None] if off.ndim else off) \
            + jnp.arange(s, dtype=jnp.int32)[None, :]
        pos = jnp.broadcast_to(pos, (b, s))
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(u)))._data \
            .reshape(b, s, self.num_heads, self.nope + self.rope)
        q = jnp.concatenate(
            [q[..., :self.nope],
             _rotary(q[..., self.nope:], pos, self.inv_freq,
                     self.magnitude)], axis=-1)
        kv = self.kv_a_proj_with_mqa(u)
        c = self.kv_a_layernorm(kv[..., :self.latent])._data
        k_r = _rotary(kv._data[..., self.latent:], pos, self.inv_freq,
                      self.magnitude)
        return Tensor(q), Tensor(in_lanes(c, k_r, self.lanes)[:, :, None])

    def _w_ukv(self):
        """``kv_b_proj`` as [latent, H, nope + v]."""
        return self.kv_b_proj.weight._data.reshape(
            self.latent, self.num_heads, self.nope + self.v_dim)

    def expanded(self, q, rows, mask):
        """The expanded form: queries ``q`` [b, s, H, nope + rope] over
        the ``rows`` [b, t, lanes] as the cache holds them, keys and
        values rebuilt a head through ``kv_b_proj``; ``mask`` [s, t]
        bool. Returns [b, s, H, v] (arrays)."""
        b, t = rows.shape[:2]
        c, k_r = rows[..., :self.latent], rows[..., self.latent:]
        kv = jnp.matmul(c, self.kv_b_proj.weight._data).reshape(
            b, t, self.num_heads, self.nope + self.v_dim)
        logits = (jnp.einsum("bshn,bthn->bhst", q[..., :self.nope],
                             kv[..., :self.nope],
                             preferred_element_type=_F32)
                  + jnp.einsum("bshr,btr->bhst", q[..., self.nope:],
                               k_r[..., :self.rope],
                               preferred_element_type=_F32)) \
            * _F32(self.scale)
        logits = jnp.where(mask[None, None], logits, _F32(-1e30))
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhst,bthv->bshv", probs.astype(q.dtype),
                          kv[..., self.nope:],
                          preferred_element_type=_F32).astype(q.dtype)

    def absorbed(self, q, attend):
        """The absorbed form for one query row a slot: ``q`` [B, H, nope
        + rope]; ``attend(q_row [B, H, lanes]) -> o_lat [B, H, latent]``
        is the attention over the rows as the cache holds them, the
        query laid out as they are. Returns [B, H, v]."""
        w = self._w_ukv()
        q_lat = jnp.einsum("bhn,chn->bhc", q[..., :self.nope],
                           w[..., :self.nope],
                           preferred_element_type=_F32).astype(q.dtype)
        o_lat = attend(in_lanes(q_lat, q[..., self.nope:], self.lanes))
        return jnp.einsum("bhc,chv->bhv", o_lat, w[..., self.nope:],
                          preferred_element_type=_F32).astype(q.dtype)

    def forward(self, u):
        """Whole sequences from position 0, causal: [b, s, d] -> [b, s,
        d] (the expanded form)."""
        b, s, _ = u.shape
        q, rows = self.qkv(u)
        pos = jnp.arange(s, dtype=jnp.int32)
        out = self.expanded(q._data, rows._data[:, :, 0],
                            pos[None, :] <= pos[:, None])
        return self.o_proj(Tensor(out.reshape(b, s, -1)))


class XingBlock(nn.Layer):
    def __init__(self, config: XingConfig, sparse):
        super().__init__()
        eps = config.rms_norm_eps
        self.hc_attn = XingHyperConnection(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=eps)
        self.self_attn = XingAttention(config)
        self.hc_mlp = XingHyperConnection(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=eps)
        attr = _normal_attr(config.initializer_range)
        self.mlp = DroplessMoE(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            norm_topk_prob=config.norm_topk_prob,
            expert_range=config.expert_range, weight_attr=attr,
            scoring="sigmoid",
            routed_scaling_factor=config.routed_scaling_factor,
            shared_width=config.moe_intermediate_size,
            # seeded and not zero: leaving it out changes the choice
            bias_attr=_normal_attr(Xing.ROUTER_BIAS_STD)) if sparse \
            else LlamaMLP(config)


class Xing(PagedServingModel):
    # the correction bias of the routers starts normal at this width (a
    # trained one is learnt by the balancing rule; the config has no key)
    ROUTER_BIAS_STD = 0.1
    # the decode step takes ``state_observer`` (its debug tap)
    decode_tap = True

    def __init__(self, config: XingConfig):
        super().__init__()
        self.config = config
        attr = _normal_attr(config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=attr)
        self.layers = nn.LayerList([
            XingBlock(config, sparse=i >= config.first_k_dense_replace)
            for i in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 weight_attr=attr, bias_attr=False)
        self._latent_rows = LatentRowSpec(config.kv_lora_rank,
                                          config.qk_rope_head_dim)
        self._sparse_layers = max(
            config.num_layers - config.first_k_dense_replace, 0)

    # -- what the cache and the scheduler adapt on ------------------------

    @property
    def latent_rows(self):
        """The row a layer caches of a token (``LatentRowSpec``): the
        cache's geometry, and the sign that what needs K and V a head
        (int8 pools, transfer frames, a mesh, the verify sweep) is
        refused."""
        return self._latent_rows

    @property
    def decode_extras(self):
        """int32 values a decode step packs behind its tokens: the rows
        routed to each expert of each sparse layer."""
        return self._sparse_layers * self.config.n_routed_experts

    def decode_expert_rows(self, packed):
        """[sparse layers, experts] of a decode step's array read to the
        host (``paged_decode_step``)."""
        return np.asarray(packed)[-self.decode_extras:].astype(
            np.int64).reshape(self._sparse_layers,
                              self.config.n_routed_experts)

    # -- the residual path: n streams ------------------------------------

    def _streams(self, x):
        """``X_0``: the embedding [b, s, d] repeated a stream."""
        with _scope("residual"):
            return Tensor(jnp.repeat(x._data[:, :, None],
                                     self.config.hc_mult, axis=2))

    def residual_read(self, blk, sublayer, x):
        hc = blk.hc_mlp if sublayer else blk.hc_attn
        xs = x._data
        pre, post, m = hc.maps(xs)
        u = Tensor(jnp.einsum("...n,...nd->...d", pre,
                              xs.astype(_F32)).astype(xs.dtype))
        tap = self.__dict__.get("_tap")
        if tap is not None:
            # ``fed``: what the stack's norm hands the sublayer (the
            # compiler keeps one of the two identical norms)
            norm = blk.post_attention_layernorm if sublayer \
                else blk.input_layernorm
            tap.append({"x": xs, "pre": pre, "post": post, "m": m,
                        "fed": norm(u)._data})
        return u, (post, m)

    def residual_write(self, blk, sublayer, x, out, mixed):
        post, m = mixed
        xs = x._data
        tap = self.__dict__.get("_tap")
        if tap is not None:
            tap[-1]["out"] = out._data
        new = jnp.einsum("...nm,...md->...nd", m, xs.astype(_F32)) \
            + post[..., None] * out._data.astype(_F32)[..., None, :]
        return Tensor(new.astype(xs.dtype))

    def residual_close(self, x):
        xs = x._data
        return Tensor(jnp.sum(xs.astype(_F32), axis=-2).astype(xs.dtype))

    # -- the feed-forward part -------------------------------------------

    def _ffn(self, blk, m, mode=None, valid=None, tag="", counts=None,
             routed=None):
        """``blk``'s feed-forward part on the normed ``m``: the dense
        SwiGLU, or the expert layer by the route ``mode`` gives."""
        if not isinstance(blk.mlp, DroplessMoE):
            y = blk.mlp(m)
        else:
            y = blk.mlp(m, kernel_mode=mode, counts_sink=counts,
                        route_sink=routed, valid=valid, kernel_tag=tag)
        tap = self.__dict__.get("_tap")
        if tap is not None and routed:
            tap[-1]["route"] = routed[-1]
        return y

    # -- the normal path: whole sequences, expanded attention, no cache ---

    def forward(self, input_ids, kernel_mode=None):
        """Logits [b, s, vocab] of ``input_ids`` [b, s], positions from
        0."""
        x = self._streams(self._embed(input_ids))
        for n, blk in enumerate(self.layers):
            with jax.named_scope(f"layers.{n}"):
                with _scope("residual"):
                    u, mixed = self.residual_read(blk, 0, x)
                    h = blk.input_layernorm(u)
                with _scope("attn"):
                    out = blk.self_attn(h)
                with _scope("residual"):
                    x = self.residual_write(blk, 0, x, out, mixed)
                    u, mixed = self.residual_read(blk, 1, x)
                    h = blk.post_attention_layernorm(u)
                with _scope("ffn"):
                    out = self._ffn(blk, h, kernel_mode)
                with _scope("residual"):
                    x = self.residual_write(blk, 1, x, out, mixed)
        with _scope("residual"):
            x = self.norm(self.residual_close(x))
        return self._logits(x)

    def generate(self, input_ids, max_new_tokens=32):
        """Greedy continuation of ``input_ids`` [1, s] by the whole
        forward a token: the plain path, no cache. Returns the new ids
        (a list)."""
        from ..core.autograd import no_grad
        ids = np.asarray(getattr(input_ids, "_data", input_ids)) \
            .reshape(1, -1).astype(np.int64)
        out = []
        with no_grad():
            for _ in range(int(max_new_tokens)):
                logits = self(Tensor(jnp.asarray(ids)))._data
                out.append(int(jnp.argmax(logits[0, -1])))
                ids = np.concatenate([ids, [[out[-1]]]], axis=1)
        return out

    # -- served path: programs over the latent cache ----------------------

    def _check_cache(self, cache):
        if cache.latent_spec != self.latent_rows \
                or cache.num_layers != self.kv_cache_layers:
            raise ValueError(
                "Xing: the cache was not built for this model: it needs "
                f"{self.kv_cache_layers} layers of latent rows "
                f"{self.latent_rows} (PagedKVCache(..., "
                "latent_rows=model.latent_rows)).")

    def _first_token(self, hidden, at, key, temp):
        return self._next_token(
            hidden, lambda logits: jnp.take_along_axis(
                logits, at[None, None, None], axis=1)[:, 0], key, temp)[0]

    def _sequence_stack(self, ids, t_start, w_start, t_total, row, pools,
                        mode, keys_of):
        """The stack over one sequence ``ids`` [1, S] at the positions
        from ``t_start`` (true positions end at ``t_total``; those from
        ``w_start`` are written, row by row through the slot's table row
        ``row``), expanded attention over ``keys_of(rows, pool) ->
        (rows [t, lanes], positions [t])`` of the layer's rows just
        computed and its pool just written."""
        from ..inference.paged import latent_prefill_write_masked
        s = ids.shape[1]
        pos_q = t_start + jnp.arange(s, dtype=jnp.int32)
        valid = Tensor(pos_q < t_total)
        fresh = {}

        def write(pool, rows):
            fresh["rows"] = rows[0, :, 0]
            return (latent_prefill_write_masked(
                pool, row, rows[0], t_start, w_start, t_total),)

        # ``expanded`` reads its layer's ``kv_b_proj``: the stack asks a
        # layer at a time, in order
        layer_of = iter(self.layers)

        def attend(q, pool):
            rows, pos_k = keys_of(fresh["rows"], pool)
            mask = (pos_k[None, :] <= pos_q[:, None]) \
                & (pos_k[None, :] < t_total)
            return next(layer_of).self_attn.expanded(q, rows[None], mask)

        return self._paged_stack(
            self._streams(self._embed(Tensor(ids))), t_start, pools,
            write, attend,
            mlp=lambda blk, m: self._ffn(blk, m, mode, valid=valid))

    def paged_prefill(self, cache, slot, prompt_ids, temperature=0.0,
                      pad_to=None, kernel_mode=None):
        """Run the prompt through the expanded forward (causal), write
        every layer's latent rows into the slot's blocks, set
        ``seq_len`` and return the first sampled token. ONE program a
        bucket ``pad_to``."""
        from ..inference.paged import resolve_paged_kernel
        self._check_cache(cache)
        mode = resolve_paged_kernel(kernel_mode)
        with self._paged_call(cache, "prefill", mode) as (call, rebind):
            with _phase("serving.prefill.forward"):
                s = int(np.asarray(prompt_ids).size)
                ids = self._padded(cache, prompt_ids, pad_to)
                tok, = call(
                    (jnp.asarray(ids), jnp.int32(s),
                     self._table_row(cache, slot)),
                    (next_key(), jnp.float32(temperature)))
            with _phase("serving.prefill.pool_write",
                        layers=cache.num_layers, tokens=ids.shape[1]):
                rebind()
                cache.seq_lens[slot] = s
        with _phase("serving.prefill.readback"):  # waits for the device
            return int(tok)

    def _build_prefill(self, quantized, mode):
        # the protocol's four lists: a latent cache's rows, then nothing
        def body(ids_arr, true_len, row, row_pools, no_v, no_ks, no_vs,
                 key, temp):
            zero = jnp.int32(0)
            s = ids_arr.shape[1]
            hidden, new, _ = self._sequence_stack(
                ids_arr, zero, zero, true_len, row,
                (row_pools, no_v, no_ks, no_vs), mode,
                lambda rows, pool: (rows, jnp.arange(s, dtype=jnp.int32)))
            return (self._first_token(hidden, true_len - 1, key, temp),
                    *new)
        return self._as_program(body, "xing.paged_prefill", 4, mode=mode)

    def paged_prefill_extend(self, cache, slot, ids, tail_start,
                             write_start, temperature=0.0, pad_to=None,
                             kernel_mode=None):
        """A prefix hit or a re-prefill: the slot's table already maps
        the latent rows of ``[0, tail_start)``; compute only the tail,
        write its rows from ``write_start`` on and attend it (expanded)
        over the slot's whole paged context, the rows just written among
        it. Sets ``seq_len`` and returns the first sampled token,
        exactly as ``paged_prefill``."""
        from ..inference.paged import resolve_paged_kernel
        self._check_cache(cache)
        mode = resolve_paged_kernel(kernel_mode)
        with _phase("serving.prefill.forward"):
            ids = np.asarray(ids).reshape(-1)
            total = ids.shape[0]
            tail = self._padded(cache, ids[tail_start:], pad_to)
            with self._paged_call(cache, "extend", mode) as (call, _):
                tok, = call(
                    (jnp.asarray(tail), jnp.int32(tail_start),
                     jnp.int32(write_start), jnp.int32(total),
                     self._table_row(cache, slot)),
                    (next_key(), jnp.float32(temperature)))
            cache.seq_lens[slot] = total
        with _phase("serving.prefill.readback"):  # waits for the device
            return int(tok)

    def _build_extend(self, quantized, mode):
        def body(tail_ids, t_start, w_start, t_total, row, row_pools,
                 no_v, no_ks, no_vs, key, temp):
            def paged_rows(rows, pool):
                t = row.shape[0] * pool.shape[1]
                return (pool[row].reshape(t, pool.shape[-1]),
                        jnp.arange(t, dtype=jnp.int32))
            hidden, new, _ = self._sequence_stack(
                tail_ids, t_start, w_start, t_total, row,
                (row_pools, no_v, no_ks, no_vs), mode, paged_rows)
            return (self._first_token(hidden, t_total - 1 - t_start, key,
                                      temp), *new)
        return self._as_program(body, "xing.paged_extend", 6, mode=mode)

    def paged_decode_step(self, cache, last_tokens, active,
                          temperature=0.0, kernel_mode=None,
                          state_observer=None):
        """One decode step of every live slot: the incoming token's
        latent row written at ``seq_len``, the absorbed attention over
        the slot's pages, the next token sampled. ``last_tokens`` is a
        token a slot [max_batch], or what the step before returned.

        Returns ONE int32 device array, ``max_batch`` tokens and behind
        them ``decode_extras`` counts (``decode_expert_rows``): every
        array a program returns costs the host some 50 us a step. The
        next step takes it as it is.

        A debug tap (docs/OBSERVABILITY.md "The latent step's tap"): the
        program also returns, for one slot, what each sublayer of this
        very step read and gave (``tap_layout``), ONE float32 device
        array that nobody reads back unless asked: ``state_observer()``,
        called under the cache's lock, gives None or ``(slot, list)``,
        and the list is appended ``(the slot's seq_len before the step,
        the array)``."""
        from ..inference.paged import resolve_paged_kernel
        self._check_cache(cache)
        mode = resolve_paged_kernel(kernel_mode)
        n = cache.max_batch
        if last_tokens.shape[0] == n:  # tokens alone: the host's
            last_tokens = np.concatenate(
                [np.asarray(last_tokens, np.int32),
                 np.zeros((self.decode_extras,), np.int32)])
        with self._paged_call(cache, "decode", mode) as (call, _):
            watched = state_observer() if state_observer else None
            packed, tap = call(
                (jnp.asarray(last_tokens, jnp.int32),),
                (cache.block_tables, jnp.asarray(cache.seq_lens),
                 jnp.asarray(active),
                 jnp.int32(watched[0] if watched else 0), next_key(),
                 jnp.float32(temperature)))
            if watched:
                watched[1].append((int(cache.seq_lens[watched[0]]), tap))
            act = np.asarray(active)
            cache.seq_lens = np.where(act, cache.seq_lens + 1,
                                      cache.seq_lens).astype(np.int32)
        return packed

    def tap_layout(self):
        """The debug tap's fields, in order: name -> shape. A layer's
        sublayer ``s`` (``a``: attention, ``f``: feed-forward) reports
        the streams it read ``x_s``, its maps ``pre_s``, ``post_s``,
        ``m_s``, its normed input ``fed_s`` and what it gave ``out_s``;
        the feed-forward part also the router's ``weights`` and
        ``experts`` (zeros in a dense layer). Behind the layers: the
        slot's ``logits`` and whether it was ``active``."""
        cfg = self.config
        n, d, k = cfg.hc_mult, cfg.hidden_size, cfg.num_experts_per_tok
        layer = {}
        for s in "af":
            layer.update({f"x_{s}": (n, d), f"pre_{s}": (n,),
                          f"post_{s}": (n,), f"m_{s}": (n, n),
                          f"fed_{s}": (d,), f"out_{s}": (d,)})
        layer.update({"weights": (k,), "experts": (k,)})
        return layer, {"logits": (cfg.vocab_size,), "active": (1,)}

    def unpack_tap(self, tap):
        """``(layers: list of dicts, tail: dict)`` of a tap array read
        to the host, by ``tap_layout``."""
        flat = np.asarray(tap, np.float32)
        layer, tail = self.tap_layout()
        at = 0

        def take(fields):
            nonlocal at
            out = {}
            for name, shape in fields.items():
                size = int(np.prod(shape))
                out[name] = flat[at:at + size].reshape(shape)
                at += size
            return out

        return [take(layer) for _ in self.layers], take(tail)

    def _build_decode(self, quantized, mode):
        cfg = self.config
        scale = cfg.softmax_scale

        def body(toks, row_pools, no_v, no_ks, no_vs, tables, lens,
                 active, probe, key, temp):
            from ..inference.paged import latent_decode_write
            b = tables.shape[0]
            seen = jnp.where(active, lens + 1, lens)
            valid = Tensor(active)
            counts, taps, logit_rows = [], [], []
            layer_of = iter(self.layers)

            def attend(q, pool):
                attn = next(layer_of).self_attn
                return attn.absorbed(
                    q[:, 0], lambda q_row: mla_decode_routed(
                        q_row, pool, tables, seen, latent=attn.latent,
                        scale=scale, kernel_mode=mode))

            def ffn(blk, m):
                routed = []
                return self._ffn(blk, m, mode, valid=valid, tag="_decode",
                                 counts=counts, routed=routed)

            self.__dict__["_tap"] = taps
            try:
                hidden, new, _ = self._paged_stack(
                    self._streams(self._embed(Tensor(toks[:b, None]))),
                    lens, (row_pools, no_v, no_ks, no_vs),
                    lambda pool, rows: (latent_decode_write(
                        pool, tables, lens, rows[:, 0], active),),
                    attend, mlp=ffn)
            finally:
                self.__dict__["_tap"] = None

            def pick(logits):
                logit_rows.append(logits[probe, 0])
                return logits[:, 0]

            nxt = self._next_token(hidden, pick, key, temp)
            packed = jnp.concatenate(
                [nxt] + [c._data.astype(jnp.int32) for c in counts])
            return (packed, self._tap_array(taps, logit_rows[0], probe,
                                            active), *new)
        return self._as_program(body, "xing.paged_decode", 2, mode=mode)

    def _tap_array(self, taps, logits, probe, active):
        """``tap_layout``'s array of the slot ``probe`` from what the
        residual path and the feed-forward parts reported (two records a
        layer, attention's then the feed-forward part's)."""
        k = self.config.num_experts_per_tok

        def of(x):
            return x[probe].astype(_F32).reshape(-1)

        parts = []
        for a, f in zip(taps[0::2], taps[1::2]):
            for rec in (a, f):
                parts += [of(rec[name]) for name in
                          ("x", "pre", "post", "m", "fed", "out")]
            if "route" in f:
                w, idx = f["route"]
                parts += [of(w._data), of(idx._data)]
            else:
                parts.append(jnp.zeros((2 * k,), _F32))
        parts += [logits.astype(_F32),
                  active[probe].astype(_F32)[None]]
        return jnp.concatenate(parts)

    def apply_serving_mesh(self, mesh):
        if mesh is not None:
            raise ValueError(
                "Xing is served on one device: a serving mesh "
                "(FLAGS_serving_mesh) shards the pools by KV head, and a "
                "latent row has none; nor are there sharding rules for "
                "the stacked experts or the stream maps.")
