"""Plain reference of the decoder XingChen-AGI's Xing4.0 publishes
(``model_type`` ``xing4_0``): latent attention (MLA), experts routed by
sigmoid scores beside a shared expert, and a residual of ``n`` streams
mixed by manifold-constrained hyper-connections (arXiv 2512.24880).
Straight ``jax.numpy`` in float32 at ``highest`` matmul precision:
expanded attention only (a head's keys and values rebuilt from the
latent row), every expert computed for every token one expert at a time,
no cache, no kernel, no batching; the head a block of the vocabulary at
a time. It reads the served model's own weights a layer at a time and is
otherwise independent of it.

For a sublayer ``F`` (attention, then the feed-forward part, each behind
its own RMSNorm) over the streams ``X`` [n, d] of a token::

    z = RMSNorm(vec(X));  Hpre = sigmoid(a_pre (z phi_pre) + b_pre)
    Hpost = 2 sigmoid(a_post (z phi_post) + b_post)
    M = sinkhorn(exp(clip(a_res mat(z phi_res) + b_res)))   columns, then rows, 20 times
    u = Hpre X;  X = M X + outer(Hpost, F(u))

``X_0`` is the embedding repeated; after the last layer the streams are
summed, normed, and meet the head. Attention, for ``u`` at position p::

    c_q = RMSNorm(u W_dq);  [q_nope, q_rope]_h = split(c_q W_uq);  q_rope <- rotary(p)
    [c, k_r] = split(u W_dkv);  c = RMSNorm(c);  k_r <- rotary(p)
    [k_nope, v]_h = split(c W_ukv)
    score_h(p, s) = scale (q_nope_h(p) . k_nope_h(s) + q_rope_h(p) . k_r(s)),  s <= p
    scale = (nope + rope)^-0.5 (0.1 mscale_all_dim ln(factor) + 1)^2
    out = concat_h(softmax(score_h) v_h) W_o

The feed-forward part is a dense SwiGLU in the first
``first_k_dense_replace`` layers; in the others, on the normed ``m``::

    s = sigmoid(m W_r);  idx = top_k(s + bias);  w = s[idx] / (sum + 1e-20) * routed_scaling_factor
    y = sum_k w_k SwiGLU_idx_k(m) + SwiGLU_shared(m)

Departures from the published description, and what it leaves open
(``assumed`` in the configuration file): matrices are read ``[in, out]``
as the program stores them; the rotary pairs are neighbouring values
(the family's port de-interleaves the same numbers first); where
``hc_eps`` enters, the order of the two normalisations, how the streams
start and end; YaRN's ramp as the family's code has it. The multi-token
prediction module is no part of this forward.

``replay_step`` is one decode step of one slot on what the served
program itself reported (``Xing.tap_layout``): every sublayer recomputed
here from the streams the program read, the attention over the rows the
program's cache holds. What the reference can be made to get wrong, for
the driver's planted faults (``FAULTS``): the latent row stored in 8
bits, a bfloat16 router, 5 Sinkhorn rounds for 20, the shared expert
dropped, the correction bias dropped, YaRN's factor of the scale
dropped, a neighbouring head's ``W_ukv``, the other sublayer's stream
maps (the replay alone: the full forward takes no ``swap_maps``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.rope_gqa_swiglu import MARGIN, margin_check

__all__ = ["weights_of", "fields_of", "forward", "replay_step",
           "stream_maps", "margin_check", "MARGIN", "rel_rms", "quiet_rms",
           "deficits", "row_errors", "LIMITS",
           "FAULTS", "yarn_inv_freq"]

_HI = jax.lax.Precision.HIGHEST
_PAD = 256
_HEAD_BLOCK = 16384

# name -> the keyword that plants it
FAULTS = {"int8_rows": {"row_bits": 8}, "bf16_router": {"router_bf16": True},
          "sinkhorn_5": {"sinkhorn_iters": 5},
          "no_shared": {"drop_shared": True},
          "no_bias": {"drop_bias": True},
          "no_yarn_scale": {"drop_yarn_scale": True},
          "other_head": {"head_shift": 1},
          "other_maps": {"swap_maps": True}}

# |program - reference| of one decode step of one slot, every sublayer
# recomputed from what the program itself read (``replay_step``), the
# largest over the layers and the sampled steps; and of the whole served
# answer against the full forward. Each limit lies between the largest
# reading of the program and the smallest of the planted fault it is
# there for, on the chip at the published widths (my chip runs, PR 34:
# the program over eight runs, the faults over two traced runs; PERF.md
# section 6 has them too, and the notes of a traced run that run's own):
# - ``mix``: the three maps of a sublayer, float32 on both sides, the
#   largest difference of an entry: 1.8e-5-3.3e-5. Five Sinkhorn rounds
#   for twenty: 1.4e-2-1.6e-2.
# (``read``, the normed input a sublayer was fed against RMSNorm(Hpre X)
# of the streams the program read, is a reading with no limit: two
# roundings to bfloat16, 2.6e-3-3.1e-3, and its planted twin, the other
# sublayer's maps (``other_maps``), breaks ``mix`` but moves the norm of
# a weighted sum too little to put a limit between them.)
# - ``row``: the compressed part of the row the step wrote into the
#   cache against the reference's of the same normed input, over the
#   half of its elements that are smallest (``quiet_rms``: bfloat16
#   rounds those hardly at all, 8 bits round them as every other); and a
#   quarter of the rotary part's relative RMS, whichever is larger:
#   1.00e-3-1.06e-3. The row kept in 8 bits: 8.5e-3-9.1e-3.
# - ``attn``: the attention sublayer's output against the expanded
#   attention over the cache's own rows, relative RMS: 4.5e-3-4.7e-3.
#   YaRN's factor of the scale dropped: 0.64-0.68; a neighbouring head's
#   ``W_ukv``: 1.46-1.47 (rows in 8 bits move it to 1.0e-2: under it).
# - ``router``: the router's weight of an expert on the program's own
#   normed input, the largest difference over the rows that are no tie:
#   0.9e-7-1.5e-7 (float32's rounding). A bfloat16 router: 3.9e-4; the
#   correction bias dropped: 0.54.
# - ``experts``: the feed-forward part's output under the program's own
#   routing, relative RMS: 3.1e-3-3.3e-3. The shared expert dropped: 1.2.
# - ``logit_rms``: the slot's logits against the full forward's at the
#   same position, relative RMS, the smallest over the sampled steps:
#   bfloat16 through every layer, 1.35e-2-1.7e-2 where no expert
#   differs (as the median of six steps it read that in nine runs, 0.097
#   in a tenth: about one step in four has a token that runs other
#   experts). The full forward without the shared expert: 0.70-0.78;
#   with a neighbouring head's ``W_ukv``: 1.37-1.43.
# - ``cached_rows``: the rows the cache holds of a request (its prefill's
#   and its decode steps') against the rows the full forward would
#   cache, a row's error as a share of the rows' scale, the median over
#   the positions, the worst layer: 1.35e-2-1.5e-2; the two faulty
#   forwards 0.62 and 1.39.
# - ``margin_p90``: the reference's maximum at a position less its logit
#   of the served token, as a share of the logits' scale, the 90th
#   percentile over the answers' positions: 4.7e-3-9.4e-3; the two
#   faulty forwards 0.38 and 0.88.
# The last three are a minimum, a median and a percentile, not maxima: a token whose
# k-th and (k+1)-th expert scores lie closer than the program's bfloat16
# activations move them runs other experts in the program than here, and
# its logits and the rows above differ by tenths of their scale with
# nothing wrong: 0.8-1.8 % of a run's positions lie over ``MARGIN``, the
# largest deficit of a run is 0.2-0.43, one sampled step of 48 read
# ``logit_rms`` 0.36, and as root mean squares the rows read 0.10-0.13.
LIMITS = {"mix": 5e-4, "row": 3e-3, "attn": 0.05,
          "router": 2.0 ** -17, "experts": 0.05, "logit_rms": 0.1,
          "cached_rows": 0.1, "margin_p90": 0.06}
# a row whose k-th and (k+1)-th biased scores lie closer than this is a
# tie no float32 rounding order resolves: left out of ``router``
_ROUTER_TIE = 1e-4


def _f32(w):
    return jnp.asarray(w).astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def _swiglu(m, gate, up, down):
    g = _mm(m, _f32(gate))
    return _mm(g * jax.nn.sigmoid(g) * _mm(m, _f32(up)), _f32(down))


def yarn_inv_freq(dim, theta, rs):
    """YaRN's frequencies [dim / 2]: ``theta^(-2i/dim)``, and the same
    over ``factor`` where a dimension turns fewer than ``beta_slow``
    times over the original context, a linear ramp between that and
    ``beta_fast`` turns."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    plain = theta ** (-i / dim)
    orig = rs["original_max_position_embeddings"]

    def dimension_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dimension_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dimension_of(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (plain * (1 - ramp) + plain / rs["factor"] * ramp).astype(
        np.float32)


def _rotary(x, pos, inv_freq, magnitude):
    """x [T, ..., dim] at positions ``pos`` [T]: neighbouring pairs."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],)
    cos = (jnp.cos(ang) * magnitude).reshape(shape)
    sin = (jnp.sin(ang) * magnitude).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _in_8_bits(row):
    """``row`` [.., w] after a round trip through int8 with one float32
    scale a row (absmax), as an 8-bit cache would keep it."""
    scale = jnp.max(jnp.abs(row), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(row / scale) * scale


# -- the pieces (float32 arrays in, float32 out; ``f``: ``fields_of``) ----

def stream_maps(x, w, f, sinkhorn_iters=None):
    """(Hpre [.., n], Hpost [.., n], M [.., n, n]) of the streams ``x``
    [.., n, d] under a sublayer's maps ``w``."""
    n = x.shape[-2]
    flat = x.reshape(x.shape[:-2] + (-1,))
    proj = _mm(_rms_norm(flat, w["norm"], f["eps"]), _f32(w["phi"]))
    a, b = _f32(w["alpha"]), _f32(w["bias"])
    pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * proj[..., 2 * n:] + b[2 * n:],
                         f["clamp"][0], f["clamp"][1]))
    m = m.reshape(m.shape[:-1] + (n, n))
    for _ in range(f["sinkhorn_iters"] if sinkhorn_iters is None
                   else sinkhorn_iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + f["hc_eps"])
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + f["hc_eps"])
    return pre, post, m


def latent_rows(un, pos, w, f, row_bits=None):
    """The rows the cache holds of the normed inputs ``un`` [T, d] at
    ``pos`` [T]: (c [T, latent], k_r [T, rope])."""
    kv = _mm(un, _f32(w["kv_a_proj"]))
    c = _rms_norm(kv[:, :f["latent"]], w["kv_a_layernorm"], f["eps"])
    k_r = _rotary(kv[:, f["latent"]:], pos, f["inv_freq"], f["magnitude"])
    if row_bits == 8:
        c, k_r = _in_8_bits(c), _in_8_bits(k_r)
    return c, k_r


def attention(un, pos, c, k_r, pos_k, w, f, drop_yarn_scale=False,
              head_shift=0):
    """The expanded attention of the normed inputs ``un`` [T, d] at
    ``pos`` over the rows ``c`` [S, latent], ``k_r`` [S, rope] at
    ``pos_k`` (negative: no row), causal by position. Returns [T, d]."""
    t, h = un.shape[0], f["heads"]
    nope, rope, vd = f["nope"], f["rope"], f["v"]
    q = _mm(_rms_norm(_mm(un, _f32(w["q_a_proj"])), w["q_a_layernorm"],
                      f["eps"]), _f32(w["q_b_proj"])).reshape(
        t, h, nope + rope)
    q_rope = _rotary(q[..., nope:], pos, f["inv_freq"], f["magnitude"])
    w_ukv = _f32(w["kv_b_proj"]).reshape(f["latent"], h, nope + vd)
    if head_shift:
        w_ukv = jnp.roll(w_ukv, -head_shift, axis=1)
    kv = jnp.einsum("sc,chx->shx", c, w_ukv, precision=_HI)
    scale = f["scale"] / f["yarn_scale"] if drop_yarn_scale else f["scale"]
    logits = (jnp.einsum("thn,shn->hts", q[..., :nope], kv[..., :nope],
                         precision=_HI)
              + jnp.einsum("thr,sr->hts", q_rope, k_r, precision=_HI)) \
        * scale
    mask = (pos_k[None, :] <= pos[:, None]) & (pos_k[None, :] >= 0)
    probs = jax.nn.softmax(jnp.where(mask[None], logits, -1e30), axis=-1)
    out = jnp.einsum("hts,shv->thv", probs, kv[..., nope:], precision=_HI)
    return _mm(out.reshape(t, h * vd), _f32(w["o_proj"]))


def router_gates(m, w, f, router_bf16=False, drop_bias=False):
    """[T, E] weight of every expert for every token: sigmoid scores,
    the ``top_k`` of score + bias kept, renormalised over the kept and
    scaled; and the biased scores themselves (for the ties)."""
    dt = jnp.bfloat16 if router_bf16 else jnp.float32
    s = jax.nn.sigmoid(_mm(m.astype(dt), w["router"].astype(dt))
                       ).astype(jnp.float32)
    biased = s if drop_bias else s + _f32(w["router_bias"])
    kth = jnp.sort(biased, axis=-1)[:, -f["top_k"]][:, None]
    kept = jnp.where(biased >= kth, s, 0.0)
    if f["norm_topk_prob"]:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    return kept * f["routed_scaling_factor"], biased


def feed_forward(m, w, gates=None, drop_shared=False):
    """The feed-forward part on the normed ``m`` [T, d]: the dense
    SwiGLU, or sum_e gates[:, e] SwiGLU_e(m) (every expert for every
    token, one expert's float32 copy live at a time) + the shared
    expert."""
    if "router" not in w:
        return _swiglu(m, w["gate_proj"], w["up_proj"], w["down_proj"])

    def one_expert(acc, ew):
        return acc + ew[3][:, None] * _swiglu(m, *ew[:3]), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (w["gate_proj"], w["up_proj"], w["down_proj"], gates.T))
    if not drop_shared:
        y = y + _swiglu(m, w["shared_gate_proj"], w["shared_up_proj"],
                        w["shared_down_proj"])
    return y


def _write(x, post, m, out):
    return jnp.einsum("...nm,...md->...nd", m, x, precision=_HI) \
        + post[..., None] * out[..., None, :]


@functools.partial(jax.jit, static_argnames=("f", "faults"))
def _layer(x, w, n, f, faults):
    """One layer on the streams ``x`` [T, n, d], positions 0..T-1 of
    which the first ``n`` are real. Returns the streams after, and the
    rows a cache would hold."""
    f, faults = f.opened(), dict(faults)
    t = x.shape[0]
    pos = jnp.arange(t, dtype=jnp.int32)
    pos_k = jnp.where(pos < n, pos, -1)
    maps = {"sinkhorn_iters": faults.get("sinkhorn_iters")}
    pre, post, m = stream_maps(x, w["hc_attn"], f, **maps)
    un = _rms_norm(jnp.einsum("tn,tnd->td", pre, x, precision=_HI),
                   w["input_layernorm"], f["eps"])
    c, k_r = latent_rows(un, pos, w, f, faults.get("row_bits"))
    x = _write(x, post, m, attention(
        un, pos, c, k_r, pos_k, w, f,
        faults.get("drop_yarn_scale", False), faults.get("head_shift", 0)))
    pre, post, m = stream_maps(x, w["hc_mlp"], f, **maps)
    fed = _rms_norm(jnp.einsum("tn,tnd->td", pre, x, precision=_HI),
                    w["post_attention_layernorm"], f["eps"])
    gates = router_gates(fed, w, f, faults.get("router_bf16", False),
                         faults.get("drop_bias", False))[0] \
        if "router" in w else None
    x = _write(x, post, m, feed_forward(
        fed, w, gates, faults.get("drop_shared", False)))
    return x, (c, k_r)


@jax.jit
def _head_block(x, norm_w, head_w, eps):
    return _mm(_rms_norm(x, norm_w, eps), _f32(head_w))


def _head(x, norm_w, head_w, eps):
    """Logits [rows, vocab] of the summed streams ``x`` [rows, d], a
    block of the vocabulary at a time (a float32 copy of the whole head
    is 1.9 GB at the published widths)."""
    v = head_w.shape[1]
    return np.concatenate([
        np.asarray(_head_block(x, norm_w, head_w[:, at:at + _HEAD_BLOCK],
                               jnp.float32(eps)))
        for at in range(0, v, _HEAD_BLOCK)], axis=1)


def _frozen(d):
    return tuple(sorted((k, tuple(v) if isinstance(v, (list, np.ndarray))
                         else v) for k, v in d.items()))


def weights_of(model):
    """The served model's parameter arrays, by name: ``embed``, ``norm``,
    ``head`` and ``layers``, a dict a layer."""
    def maps(hc):
        return {"norm": hc.norm_weight._data, "phi": hc.phi._data,
                "alpha": hc.alpha._data, "bias": hc.bias._data}

    layers = []
    for blk in model.layers:
        a, e = blk.self_attn, blk.mlp
        w = {"hc_attn": maps(blk.hc_attn), "hc_mlp": maps(blk.hc_mlp),
             "input_layernorm": blk.input_layernorm.weight._data,
             "post_attention_layernorm":
                 blk.post_attention_layernorm.weight._data,
             "q_a_proj": a.q_a_proj.weight._data,
             "q_a_layernorm": a.q_a_layernorm.weight._data,
             "q_b_proj": a.q_b_proj.weight._data,
             "kv_a_proj": a.kv_a_proj_with_mqa.weight._data,
             "kv_a_layernorm": a.kv_a_layernorm.weight._data,
             "kv_b_proj": a.kv_b_proj.weight._data,
             "o_proj": a.o_proj.weight._data}
        if hasattr(e, "router"):
            w.update({
                "router": e.router._data,
                "router_bias": e.e_score_correction_bias._data,
                "gate_proj": e.gate_proj._data, "up_proj": e.up_proj._data,
                "down_proj": e.down_proj._data,
                "shared_gate_proj": e.shared_gate_proj._data,
                "shared_up_proj": e.shared_up_proj._data,
                "shared_down_proj": e.shared_down_proj._data})
        else:
            w.update({"gate_proj": e.gate_proj.weight._data,
                      "up_proj": e.up_proj.weight._data,
                      "down_proj": e.down_proj.weight._data})
        layers.append(w)
    return {"embed": model.embed_tokens.weight._data,
            "norm": model.norm.weight._data,
            "head": model.lm_head.weight._data, "layers": layers}


def fields_of(config):
    """What the pieces need of a configuration file's keys (the Hugging
    Face names), as a dict of plain numbers and tuples."""
    rs = config["rope_scaling"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    yarn = (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0) ** 2
    return {
        "heads": config["num_attention_heads"], "nope": nope, "rope": rope,
        "v": config["v_head_dim"], "latent": config["kv_lora_rank"],
        "eps": config["rms_norm_eps"], "hc_eps": config["hc_eps"],
        "sinkhorn_iters": config["hc_sinkhorn_iters"],
        "clamp": (float(config["mhc_h_res_clamp_min"]),
                  float(config["mhc_h_res_clamp_max"])),
        "top_k": config["num_experts_per_tok"],
        "norm_topk_prob": bool(config["norm_topk_prob"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "scale": (nope + rope) ** -0.5 * yarn, "yarn_scale": yarn,
        "magnitude": (0.1 * rs["mscale"] * math.log(rs["factor"]) + 1.0)
        / (0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0),
        "inv_freq": tuple(float(v) for v in yarn_inv_freq(
            rope, float(config["rope_theta"]), rs))}


class _Fields(dict):
    """``fields_of``'s dict (or a fault's keywords), hashable for
    ``jit``'s static arguments."""

    def __hash__(self):
        return hash(_frozen(self))

    def opened(self):
        """A plain dict, the frequencies an array again."""
        f = dict(self)
        if "inv_freq" in f:
            f["inv_freq"] = np.asarray(f["inv_freq"], np.float32)
        return f


def forward(weights, fields, ids, rows, **faults):
    """The full forward over ``ids``: float32 logits [len(rows), vocab]
    at the positions ``rows``, and the rows a cache would hold, a layer
    (c [T, latent], k_r [T, rope])."""
    ids = np.asarray(ids, np.int64)
    n = len(ids)
    padded = np.zeros((-(-n // _PAD) * _PAD,), np.int32)
    padded[:n] = ids
    f = _Fields(fields)
    with jax.default_matmul_precision("highest"):
        e = weights["embed"][jnp.asarray(padded)].astype(jnp.float32)
        x = jnp.repeat(e[:, None], _streams_of(weights), axis=1)
        cached = []
        for w in weights["layers"]:
            x, held = _layer(x, w, jnp.int32(n), f, _Fields(faults))
            cached.append(tuple(np.asarray(a[:n]) for a in held))
        summed = jnp.sum(x, axis=1)[jnp.asarray(rows, jnp.int32)]
        return _head(summed, weights["norm"], weights["head"],
                     fields["eps"]), cached


def _streams_of(weights):
    """n, from a sublayer's bias [n (n + 2)]."""
    size = int(weights["layers"][0]["hc_attn"]["bias"].shape[0])
    return int(round(math.sqrt(size + 1) - 1))


def quiet_rms(got, want):
    """The root mean square of ``got - want`` over the half of the
    elements where ``|want|`` is smallest, as a share of the root mean
    square of all of ``want``. A rounding to bfloat16 errs by a share of
    each element, so hardly at all there; a row kept in 8 bits errs by
    a share of the row's largest element, everywhere the same."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    quiet = np.abs(want) <= np.median(np.abs(want))
    return float(np.sqrt(np.mean((got - want)[quiet] ** 2)
                         / max(np.mean(want ** 2), 1e-300)))


def deficits(ref_logits, served):
    """For each served token, the reference's maximum at its position
    less the reference's logit of it, as a share of the logits' scale
    (their largest magnitude over the rows): ``margin_check``'s
    deficits, one a position. ``ref_logits`` [n, vocab] are the rows
    that chose ``served`` [n]."""
    ref = np.asarray(ref_logits, np.float32)
    served = np.asarray(served, np.int64)
    return (ref.max(axis=-1) - ref[np.arange(len(served)), served]) \
        / float(np.abs(ref).max())


def row_errors(got, want):
    """|got - want| of each row as a share of the root mean square of
    ``want``'s rows' norms: one number a position."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / max(
        float(np.sqrt(np.mean(np.sum(want * want, axis=-1)))), 1e-300)


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)
                         / max(np.mean(want ** 2), 1e-300)))


@functools.partial(jax.jit, static_argnames=("f", "faults"))
def _replay(rec, pos, keys_c, keys_r, w, f, faults):
    f, faults = f.opened(), dict(faults)
    maps = {"sinkhorn_iters": faults.get("sinkhorn_iters")}
    at = jnp.reshape(pos, (1,))
    out = {}
    hc_a, hc_f = (w["hc_mlp"], w["hc_attn"]) if faults.get("swap_maps") \
        else (w["hc_attn"], w["hc_mlp"])
    x = rec["x_a"][None]
    out["pre_a"], out["post_a"], out["m_a"] = (
        a[0] for a in stream_maps(x, hc_a, f, **maps))
    out["fed_a"] = _rms_norm(
        jnp.einsum("tn,tnd->td", out["pre_a"][None], x, precision=_HI),
        w["input_layernorm"], f["eps"])[0]
    un = rec["fed_a"][None]
    c, k_r = latent_rows(un, at, w, f, faults.get("row_bits"))
    out["row_c"], out["row_r"] = c[0], k_r[0]
    pos_k = jnp.arange(keys_c.shape[0], dtype=jnp.int32)
    pos_k = jnp.where(pos_k <= pos, pos_k, -1)
    if faults.get("row_bits") == 8:
        keys_c, keys_r = _in_8_bits(keys_c), _in_8_bits(keys_r)
    out["out_a"] = attention(
        un, at, keys_c, keys_r, pos_k, w, f,
        faults.get("drop_yarn_scale", False),
        faults.get("head_shift", 0))[0]
    x = rec["x_f"][None]
    out["pre_f"], out["post_f"], out["m_f"] = (
        a[0] for a in stream_maps(x, hc_f, f, **maps))
    out["fed_f"] = _rms_norm(
        jnp.einsum("tn,tnd->td", out["pre_f"][None], x, precision=_HI),
        w["post_attention_layernorm"], f["eps"])[0]
    fed = rec["fed_f"][None]
    if "router" in w:
        gates, biased = router_gates(
            fed, w, f, faults.get("router_bf16", False),
            faults.get("drop_bias", False))
        out["gates"], out["biased"] = gates[0], biased[0]
        program = jnp.zeros_like(gates).at[
            0, rec["experts"].astype(jnp.int32)].set(rec["weights"])
        out["out_f"] = feed_forward(fed, w, program,
                                    faults.get("drop_shared", False))[0]
    else:
        out["out_f"] = feed_forward(fed, w)[0]
    return out


def replay_step(weights, fields, layers, pos, held, **faults):
    """One decode step of one slot at position ``pos`` against what the
    program reported of it: ``layers`` is ``Xing.unpack_tap``'s list (a
    dict a layer) and ``held`` the rows the slot's cache holds a layer,
    (c [>= pos + 1, latent], k_r [.., rope]), the step's own among them.
    The maps and what a sublayer reads are recomputed from the streams
    the program read, each sublayer from the normed input the program
    fed it; the attention runs over the cache's own rows. Returns the
    readings ``mix``, ``read``, ``row``, ``attn``, ``router``,
    ``experts``, each the largest over the layers (``LIMITS``)."""
    f = _Fields(fields)
    worst = dict.fromkeys(
        ("mix", "read", "row", "attn", "router", "experts"), 0.0)
    rows = -(-(pos + 1) // _PAD) * _PAD

    def padded(a):
        a = np.asarray(a, np.float32)[:rows]
        return np.pad(a, ((0, rows - a.shape[0]), (0, 0)))

    with jax.default_matmul_precision("highest"):
        for rec, w, (keys_c, keys_r) in zip(layers, weights["layers"],
                                            held):
            keys_c, keys_r = padded(keys_c), padded(keys_r)
            ref = {k: np.asarray(v) for k, v in _replay(
                {k: jnp.asarray(v) for k, v in rec.items()},
                jnp.int32(pos), jnp.asarray(keys_c), jnp.asarray(keys_r),
                w, f, _Fields(faults)).items()}
            mix = max(float(np.abs(ref[f"{name}_{s}"]
                                   - rec[f"{name}_{s}"]).max())
                      for name in ("pre", "post", "m") for s in "af")
            reads = {"mix": mix,
                     "read": max(rel_rms(rec[k], ref[k])
                                 for k in ("fed_a", "fed_f")),
                     "row": max(quiet_rms(keys_c[pos], ref["row_c"]),
                                rel_rms(keys_r[pos], ref["row_r"]) / 4),
                     "attn": rel_rms(rec["out_a"], ref["out_a"]),
                     "experts": rel_rms(rec["out_f"], ref["out_f"])}
            if "gates" in ref:
                k = fields["top_k"]
                ranked = np.sort(ref["biased"])
                if ranked[-k] - ranked[-k - 1] > _ROUTER_TIE * ranked[-k]:
                    dense = np.zeros_like(ref["gates"])
                    dense[rec["experts"].astype(np.int64)] = rec["weights"]
                    reads["router"] = float(
                        np.abs(dense - ref["gates"]).max())
            for name, v in reads.items():
                worst[name] = max(worst[name], v)
    return worst
