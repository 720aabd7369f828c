"""Plain reference of the decoder AI21's Jamba publishes (``model_type``
``jamba``, dense members: ``num_experts`` 1): pre-RMSNorm blocks whose
mixer is a state-space (Mamba-1) layer, or grouped-query attention with
no positional encoding where ``i % attn_layer_period ==
attn_layer_offset``, each followed by a SwiGLU feed-forward; a final
norm and a tied head. Straight ``jax.numpy`` in float32 at ``highest``
matmul precision: the recurrence a ``lax.scan`` of one position a step,
full causal attention, no cache, no kernel, no batching. It reads the
served model's own weights a layer at a time (a float32 copy of all of
them is 12 GB) and is otherwise independent of it: the state here is
``h`` [E, N], as the equations have it.

The state-space mixer on ``x[1..T]`` (``E`` channels, ``N`` states, rank
``R``, a convolution of ``K`` taps)::

    [u, z] = x W_in;  c_t = silu(b + sum_{j<K} w[j] * u_{t-K+1+j})
    [r, B, C]_t = c_t W_x, each RMS-normed with a learned weight
    delta_t = softplus(r_t W_dt + b_dt);  A = -exp(A_log)
    h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * c_t) (x) B_t
    y_t = h_t C_t + D * c_t;  out_t = (y_t * silu(z_t)) W_out

Departures from the Hugging Face port, noted: matrices are read
``[in, out]`` and the convolution's weight ``[K, E]``, as the program
stores them (the port: ``[out, in]`` and ``[E, 1, K]``; the same numbers);
the port's fast path fuses the convolution and the scan in CUDA kernels
and keeps ``h`` in float32 as here; ``delta``'s bias is added in float32.

A sequence is padded to a whole number of ``_PAD`` positions so that the
layers compile once; ``n`` says where it ends: positions from ``n`` on
do not exist (they leave ``h`` alone, and everything is causal).

``replay`` is the recurrence alone, on inputs that are handed in: the
driver feeds it what the served program's own decode steps were fed.

What the reference can be made to get wrong, for the driver's planted
faults: ``h_bits`` (``h`` rounded after every step to that many bits of
mantissa: 7 is bfloat16's), ``h0`` (a state and a tail to start from
where a fresh sequence starts from zero). A padded prefill that advances
``h`` is planted by handing in the ids with the padding as real tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.rope_gqa_swiglu import MARGIN, margin_check

__all__ = ["weights_of", "fields_of", "forward", "replay", "margin_check",
           "MARGIN", "rel_rms", "slowest", "LIMITS"]

_HI = jax.lax.Precision.HIGHEST
_PAD = 256

# |program - reference| as relative RMS, a state-space layer, the largest
# over the layers (``rel_rms``). Each limit lies between the largest
# reading of the program and the smallest of the planted fault it is
# there for, on the chip at the published widths (my chip runs, PR 32:
# the program over the ten runs of the committed program, the faults
# over its two traced runs; PERF.md section 6 has them too, and the
# notes of a traced run have that run's own):
# - ``start_h``: ``h`` just after the prefill against the full forward
#   over the same tokens. The program's activations are bfloat16 and a
#   layer's ``h`` hangs on every layer below: 0.052-0.059. Padding that
#   advances ``h``: 1.09-1.11.
# - ``start_h_slow``: the same over the 64th of a layer's elements that
#   forget slowest (``slowest``), where what a slot held before is left
#   longest: 0.052-0.056; another request's state left in the slot:
#   0.68-0.78 (over all elements that fault reads 0.12-0.16, too near
#   the program).
# - ``replay_h``: ``h`` after the request's last step against the float32
#   recurrence replayed from that early state on the program's own
#   inputs: two float32 sums of the same terms, 0.0 (bit for bit) over
#   some 500 steps; ``h`` rounded to bfloat16 a step: 5.3e-3-5.6e-3.
# - ``fed_inputs``: what the program's decode steps report of delta, c
#   and B against what the full forward fed its own recurrence at those
#   positions, the largest of the three: bfloat16 activations again,
#   0.036-0.037; the reports of the layer before read as this layer's:
#   1.43.
# - ``end_h``, ``end_tail``: the state after the last step against the
#   full forward's: 0.050-0.067 and 0.034-0.038. They hold the decode
#   steps' state to the forward at the far end; no planted fault rests
#   on them (padding that advanced ``h`` has mostly decayed by then:
#   0.061-0.29 and 0.039-0.18).
LIMITS = {"start_h": 0.25, "start_h_slow": 0.2, "replay_h": 3e-4,
          "fed_inputs": 0.25, "end_h": 0.15, "end_tail": 0.07}


def _f32(w):
    return w.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rounded(h, bits):
    """``h`` with ``bits`` of mantissa (7: bfloat16). Not a pair of
    casts: the compiler may keep the excess precision of those."""
    return h if bits is None else jax.lax.reduce_precision(
        h, exponent_bits=8, mantissa_bits=bits)


def _feed_forward(x, w, eps):
    h = _rms_norm(x, w["pre_ff_layernorm"], eps)
    gate, up = _mm(h, _f32(w["gate_proj"])), _mm(h, _f32(w["up_proj"]))
    return x + _mm(_silu(gate) * up, _f32(w["down_proj"]))


@functools.partial(jax.jit, static_argnames=("eps", "h_bits"))
def _mamba_block(x, w, n, snap, h0, tail0, *, eps, h_bits):
    """One state-space block on x [T, hidden]. Returns (x, h [E, N]
    after position n - 1 and after position snap - 1, the K - 1 rows of
    u before n, and what moves h at every position: delta, c and B side
    by side [T, 2 E + N])."""
    t = x.shape[0]
    conv_w = _f32(w["conv_weight"])
    k, e = conv_w.shape
    a = -jnp.exp(_f32(w["A_log"]))                       # [E, N]
    states = a.shape[1]
    rank = w["dt_proj"].shape[0]
    u, z = jnp.split(_mm(_rms_norm(x, w["input_layernorm"], eps),
                         _f32(w["in_proj"])), 2, axis=-1)
    cat = jnp.concatenate([tail0, u], axis=0)
    c = _silu(_f32(w["conv_bias"]) + sum(conv_w[j] * cat[j:j + t]
                                         for j in range(k)))
    tail = jax.lax.dynamic_slice_in_dim(cat, n, k - 1, axis=0)
    rbc = _mm(c, _f32(w["x_proj"]))
    r = _rms_norm(rbc[:, :rank], w["dt_layernorm"], eps)
    b = _rms_norm(rbc[:, rank:rank + states], w["b_layernorm"], eps)
    cm = _rms_norm(rbc[:, rank + states:], w["c_layernorm"], eps)
    delta = jax.nn.softplus(_mm(r, _f32(w["dt_proj"]))
                            + _f32(w["dt_bias"]))

    def step(carry, inp):
        h, early = carry
        i, dt, ct, bt, cmt = inp
        new = _rounded(jnp.exp(dt[:, None] * a) * h
                       + (dt * ct)[:, None] * bt[None], h_bits)
        new = jnp.where(i < n, new, h)
        return (new, jnp.where(i == snap - 1, new, early)), \
            jnp.matmul(new, cmt, precision=_HI)

    (h, early), y = jax.lax.scan(
        step, (h0, h0), (jnp.arange(t, dtype=jnp.int32), delta, c, b, cm))
    y = y + _f32(w["D"]) * c
    x = x + _mm(y * _silu(z), _f32(w["out_proj"]))
    return _feed_forward(x, w, eps), h, early, tail, \
        jnp.concatenate([delta, c, b], axis=-1)


@functools.partial(jax.jit, static_argnames=("h_bits",))
def _replay(h0, a_log, delta, c, b, *, h_bits):
    a = -jnp.exp(_f32(a_log))

    def step(h, inp):
        dt, ct, bt = inp
        return _rounded(jnp.exp(dt[:, None] * a) * h
                        + (dt * ct)[:, None] * bt[None], h_bits), None

    return jax.lax.scan(step, h0, (delta, c, b))[0]


def replay(weights, h0, fed, h_bits=None):
    """The recurrence alone, from ``h0`` [state layers, E, N] over the
    steps ``fed`` [steps, state layers, 2 E + N (+ 1)] (a step and
    layer: delta [E], c [E], B [N], as the served decode program
    reports them). Returns h [state layers, E, N] after the last."""
    fed = np.asarray(fed, np.float32)
    a_logs = [w["A_log"] for w in weights[1] if "A_log" in w]
    e, n = a_logs[0].shape
    return np.stack([np.asarray(_replay(
        jnp.asarray(h0[j], jnp.float32), a_log, fed[:, j, :e],
        fed[:, j, e:2 * e], fed[:, j, 2 * e:2 * e + n], h_bits=h_bits))
        for j, a_log in enumerate(a_logs)])


@functools.partial(jax.jit, static_argnames=("eps", "num_heads",
                                             "num_kv_heads"))
def _attention_block(x, w, *, eps, num_heads, num_kv_heads):
    t, hidden = x.shape
    d = hidden // num_heads
    h = _rms_norm(x, w["input_layernorm"], eps)
    q = _mm(h, _f32(w["q_proj"])).reshape(t, num_heads, d)
    kk = _mm(h, _f32(w["k_proj"])).reshape(t, num_kv_heads, d)
    v = _mm(h, _f32(w["v_proj"])).reshape(t, num_kv_heads, d)
    rep = num_heads // num_kv_heads
    kk, v = jnp.repeat(kk, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, kk, precision=_HI) / (d ** 0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI)
    x = x + _mm(attn.reshape(t, hidden), _f32(w["o_proj"]))
    return _feed_forward(x, w, eps)


@jax.jit
def _embed(table, ids):
    return _f32(table)[ids]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_w, table, *, eps):
    return _mm(_rms_norm(x, norm_w, eps), _f32(table).T)


def weights_of(model):
    """(embedding, [a dict a block], final norm) read from a
    ``paddle_tpu.models.Jamba``: parameter arrays only, as served."""
    blocks = []
    for layer in model.layers:
        m = layer.mlp
        w = {"input_layernorm": layer.input_layernorm.weight._data,
             "pre_ff_layernorm": layer.pre_ff_layernorm.weight._data,
             "gate_proj": m.gate_proj.weight._data,
             "up_proj": m.up_proj.weight._data,
             "down_proj": m.down_proj.weight._data}
        if layer.self_attn is not None:
            a = layer.self_attn
            w.update(q_proj=a.q_proj.weight._data,
                     k_proj=a.k_proj.weight._data,
                     v_proj=a.v_proj.weight._data,
                     o_proj=a.o_proj.weight._data)
        else:
            x = layer.mixer
            w.update(in_proj=x.in_proj.weight._data,
                     conv_weight=x.conv_weight._data,
                     conv_bias=x.conv_bias._data,
                     x_proj=x.x_proj.weight._data,
                     dt_layernorm=x.dt_layernorm.weight._data,
                     b_layernorm=x.b_layernorm.weight._data,
                     c_layernorm=x.c_layernorm.weight._data,
                     dt_proj=x.dt_proj.weight._data,
                     dt_bias=x.dt_proj.bias._data,
                     A_log=x.A_log._data, D=x.D._data,
                     out_proj=x.out_proj.weight._data)
        blocks.append(w)
    return model.embed_tokens.weight._data, blocks, model.norm.weight._data


def fields_of(config):
    """What the layers need of a configuration file's keys."""
    return {"num_heads": int(config["num_attention_heads"]),
            "num_kv_heads": int(config["num_key_value_heads"]),
            "eps": float(config["rms_norm_eps"])}


def forward(weights, fields, ids, rows, h_bits=None, h0=None, snap=0,
            fed=False):
    """The full forward pass over ``ids`` from zero state (or from
    ``h0``: (h [state layers, E, N], tail [state layers, K - 1, E])).
    Returns, as numpy, (float32 logits [len(rows), vocab] at the
    positions ``rows``, h [state layers, E, N] after the last position,
    the last K - 1 rows of u [state layers, K - 1, E]), with ``snap``
    also h after the first ``snap`` positions, and with ``fed`` also what
    moved h at each position from ``snap`` on [state layers, positions,
    2 E + N] (delta, c, B: ``replay``'s layout)."""
    table, blocks, norm_w = weights
    ids = np.asarray(ids).reshape(-1)
    n = int(ids.size)
    padded = np.zeros((-(-n // _PAD) * _PAD,), np.int32)
    padded[:n] = ids
    x = _embed(table, jnp.asarray(padded))
    eps = fields["eps"]
    hs, early, tails, moved = [], [], [], []
    for w in blocks:
        if "q_proj" in w:
            x = _attention_block(x, w, eps=eps,
                                 num_heads=fields["num_heads"],
                                 num_kv_heads=fields["num_kv_heads"])
            continue
        k, e = w["conv_weight"].shape
        j = len(hs)
        start = (jnp.zeros((e, w["A_log"].shape[1]), jnp.float32),
                 jnp.zeros((k - 1, e), jnp.float32)) if h0 is None else \
            (jnp.asarray(h0[0][j], jnp.float32),
             jnp.asarray(h0[1][j], jnp.float32))
        x, h, at_snap, tail, inputs = _mamba_block(
            x, w, jnp.int32(n), jnp.int32(snap), *start, eps=eps,
            h_bits=h_bits)
        if fed:
            moved.append(np.asarray(inputs[snap:n]))
        hs.append(np.asarray(h))
        early.append(np.asarray(at_snap))
        tails.append(np.asarray(tail))
    logits = _head(x[jnp.asarray(rows, jnp.int32)], norm_w, table, eps=eps)
    out = (np.asarray(logits), np.stack(hs), np.stack(tails))
    if snap:
        out += (np.stack(early),)
    return (*out, np.stack(moved)) if fed else out


def slowest(weights, share=1.0 / 64):
    """[state layers, E, N] bool: a layer's ``share`` of elements of
    ``h`` that forget slowest, by the weights alone (an element decays
    by ``exp(-softplus(dt_bias[e]) * exp(A_log[e, n]))`` a step where
    the input moves delta little): what another request left in a slot
    stays longest there."""
    masks = []
    for w in weights[1]:
        if "A_log" not in w:
            continue
        rate = np.log1p(np.exp(np.asarray(_f32(w["dt_bias"]))))[:, None] \
            * np.exp(np.asarray(_f32(w["A_log"])))
        masks.append(rate <= np.quantile(rate, share))
    return np.stack(masks)


def rel_rms(got, want, mask=None):
    """|got - want| over |want|, root mean squares, a leading row (a
    state-space layer) each, over the elements ``mask`` keeps; the
    largest."""
    rows = []
    for j in range(len(want)):
        keep = slice(None) if mask is None else mask[j]
        g = np.asarray(got[j], np.float64)[keep]
        w = np.asarray(want[j], np.float64)[keep]
        rows.append(np.sqrt(np.mean((g - w) ** 2) / np.mean(w ** 2)))
    return float(max(rows))
