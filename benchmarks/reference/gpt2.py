"""Plain reference of GPT-2 (Radford et al. 2019): learned positions,
pre-LayerNorm blocks of multi-head attention and a 4x GELU (tanh form)
feed-forward with biases, head tied to the token table; next-token cross
entropy. Straight ``jax.numpy`` in float32 at ``highest`` matmul
precision, one sequence at a time: no kernels, no fused head. Reads the
trained model's own weights (``[in, out]`` matrices; q, k, v stacked
along the output as [3, heads, head_dim], as the program lays them out).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _block(x, w, num_heads, eps):
    s, hidden = x.shape
    d = hidden // num_heads
    h = _ln(x, w["ln_1.weight"], w["ln_1.bias"], eps)
    qkv = (_mm(h, w["attn.qkv_proj.weight"]) + w["attn.qkv_proj.bias"]
           ).reshape(s, 3, num_heads, d)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / (d ** 0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI)
    x = x + _mm(attn.reshape(s, hidden), w["attn.out_proj.weight"]) \
        + w["attn.out_proj.bias"]
    h = _ln(x, w["ln_2.weight"], w["ln_2.bias"], eps)
    h = jax.nn.gelu(_mm(h, w["mlp.fc_in.weight"]) + w["mlp.fc_in.bias"],
                    approximate=True)
    return x + _mm(h, w["mlp.fc_out.weight"]) + w["mlp.fc_out.bias"]


@functools.partial(jax.jit, static_argnames=("num_heads", "eps"))
def _sequence_nll(weights, ids, num_heads, eps):
    """Sum over positions of -log p(ids[t+1] | ids[:t+1])."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    x = w["wte.weight"][ids] + w["wpe.weight"][:ids.shape[0]]
    for blk in w["h"]:
        x = _block(x, blk, num_heads, eps)
    x = _ln(x, w["ln_f.weight"], w["ln_f.bias"], eps)
    logp = jax.nn.log_softmax(_mm(x[:-1], w["wte.weight"].T), -1)
    return -jnp.take_along_axis(logp, ids[1:, None], -1).sum()


def weights_of(model):
    """Parameter arrays of a ``paddle_tpu.models.GPT`` as a tree."""
    flat = {n: p._data for n, p in model.named_parameters()}
    depth = 1 + max(int(n.split(".")[1]) for n in flat if n.startswith("h."))
    tree = {n: a for n, a in flat.items() if not n.startswith("h.")}
    tree["h"] = [{n.split(".", 2)[2]: a for n, a in flat.items()
                  if n.startswith(f"h.{i}.")} for i in range(depth)]
    return tree


def loss(weights, fields, batch):
    """Mean next-token cross entropy over ``batch`` [b, s] (labels are
    the inputs shifted by one, as ``GPT.loss(ids, ids)``)."""
    total = 0.0
    for row in batch:
        total += float(_sequence_nll(
            weights, jnp.asarray(row, jnp.int32),
            num_heads=int(fields["num_heads"]),
            eps=float(fields["layer_norm_epsilon"])))
    return total / (batch.shape[0] * (batch.shape[1] - 1))


# |bf16 program - float32 reference| of the first step's loss, relative.
# The loss is a mean over thousands of positions, so the roundings of the
# bfloat16 forward (2^-9 relative each) largely average out and what is
# left is their bias: parts in a thousand. 2^-8 holds that and fails a
# forward in a lower precision; it cannot see a fault that leaves the loss
# of a randomly initialised model near ln(vocab), which is what the
# falling loss and the kernels-present checks are for.
LOSS_TOLERANCE = 2.0 ** -8
