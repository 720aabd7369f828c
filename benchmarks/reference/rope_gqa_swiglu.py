"""Plain reference of the decoder Mistral-7B publishes: pre-RMSNorm
blocks of grouped-query attention with rotary positions and a SwiGLU
feed-forward, no bias, untied head. Straight ``jax.numpy`` in float32 at
``highest`` matmul precision: no cache, no kernels, no batching. It reads
the served model's own weights (``[in, out]`` matrices, as the program
stores them) and is otherwise independent of it.

Departure from the Hugging Face port, noted: the rotation pairs
neighbouring features ``(2i, 2i+1)``, as Mistral's own reference code
(``mistral-inference``, complex pairs) and the program do; the port pairs
``(i, i + d/2)`` and permutes the q/k weights to match. With seeded random
weights the two are the same model up to that permutation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [s, heads, d]; position = row index."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _f32(w):
    return w.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def block(x, w, *, num_heads, num_kv_heads, rope_theta, eps):
    """One decoder block on x [s, hidden]; w: dict of this block's
    weights."""
    s, hidden = x.shape
    d = hidden // num_heads
    h = _rms_norm(x, _f32(w["input_layernorm"]), eps)
    q = _mm(h, _f32(w["q_proj"])).reshape(s, num_heads, d)
    k = _mm(h, _f32(w["k_proj"])).reshape(s, num_kv_heads, d)
    v = _mm(h, _f32(w["v_proj"])).reshape(s, num_kv_heads, d)
    q, k = _rope(q, rope_theta), _rope(k, rope_theta)
    rep = num_heads // num_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / (d ** 0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI)
    x = x + _mm(attn.reshape(s, hidden), _f32(w["o_proj"]))
    h = _rms_norm(x, _f32(w["post_attention_layernorm"]), eps)
    gate, up = _mm(h, _f32(w["gate_proj"])), _mm(h, _f32(w["up_proj"]))
    return x + _mm(jax.nn.silu(gate) * up, _f32(w["down_proj"]))


_block = jax.jit(block, static_argnames=("num_heads", "num_kv_heads",
                                         "rope_theta", "eps"))


@jax.jit
def _embed(table, ids):
    return _f32(table)[ids]


@jax.jit
def _head(x, norm_w, head_w, eps):
    return _mm(_rms_norm(x, _f32(norm_w), eps), _f32(head_w))


def weights_of(model):
    """(embedding, [per-block dicts], final norm, head) read from a
    ``paddle_tpu.models.Llama``: parameter arrays only."""
    blocks = []
    for layer in model.layers:
        a, m = layer.self_attn, layer.mlp
        blocks.append({
            "input_layernorm": layer.input_layernorm.weight._data,
            "post_attention_layernorm":
                layer.post_attention_layernorm.weight._data,
            "q_proj": a.q_proj.weight._data, "k_proj": a.k_proj.weight._data,
            "v_proj": a.v_proj.weight._data, "o_proj": a.o_proj.weight._data,
            "gate_proj": m.gate_proj.weight._data,
            "up_proj": m.up_proj.weight._data,
            "down_proj": m.down_proj.weight._data})
    return (model.embed_tokens.weight._data, blocks,
            model.norm.weight._data, model.lm_head.weight._data)


def logits(weights, fields, ids):
    """float32 logits [len(ids), vocab] of the full forward pass over
    ``ids``; ``fields`` are the configuration's (num_heads, num_kv_heads,
    rope_theta, rms_norm_eps). Blocks run one jitted call each, so one
    block's float32 copy is on the device at a time."""
    table, blocks, norm_w, head_w = weights
    x = _embed(table, jnp.asarray(ids, jnp.int32))
    for w in blocks:
        x = _block(x, w, num_heads=int(fields["num_heads"]),
                   num_kv_heads=int(fields["num_kv_heads"]),
                   rope_theta=float(fields["rope_theta"]),
                   eps=float(fields["rms_norm_eps"]))
    return _head(x, norm_w, head_w, jnp.float32(fields["rms_norm_eps"]))


# |bf16 program - float32 reference| logits, as a share of max|reference|.
# Every product and sum of the program rounds to bfloat16 (2^-9 relative);
# through 8 to 16 layers at these widths that grows to a few hundredths of
# the logits' scale: 2.5e-2 to 3.0e-2 between two bf16 layouts (chip, PR
# 21), 4.7e-2 against float32 at 16 layers (chip run kept in
# chiprun_out/.last_call.json from PR 23). 2^-4 passes bfloat16 and fails
# fp8 (2^-4 a rounding) and anything that is an error of the scale itself.
LOGIT_ERROR = 2.0 ** -4


def margin_check(ref_logits, prompt_len, generated):
    """How far the served tokens are from the reference's choice, without
    asking the engine for logits. ``ref_logits``: the reference over
    prompt + generated; token ``generated[j]`` was chosen at position
    ``prompt_len - 1 + j``. Returns (worst deficit as a share of the
    logits' scale, that scale): deficit = reference maximum at the
    position less the reference logit of the served token.

    Greedy serving picks t with sys[t] >= sys[r] for the reference's
    choice r, so ref[r] - ref[t] <= 2 * max|sys - ref|: a correct bf16
    program stays under ``2 * LOGIT_ERROR`` of the scale, whatever
    rounding does to the order of near-ties (token equality would flip on
    those). A random token misses by the whole spread of the logits,
    about four standard deviations, some ten times the margin."""
    import numpy as np

    ref = np.asarray(ref_logits, np.float32)
    rows = ref[prompt_len - 1: prompt_len - 1 + len(generated)]
    scale = float(np.abs(rows).max())
    deficit = rows.max(axis=-1) - rows[np.arange(len(generated)),
                                       np.asarray(generated)]
    return float(deficit.max()) / scale, scale


MARGIN = 2.0 * LOGIT_ERROR
