"""Plain reference of SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``) and
of its generation by diffusion over blocks. Straight ``jax.numpy`` in
float32 at ``highest`` matmul precision: no kernel, no cache, no
batching, no sorting by expert. It reads the served model's own weights
(``[in, out]`` matrices, experts stacked ``[E, in, out]``) and is
otherwise independent of it.

A layer: pre-RMSNorm; q, k, v without bias, heads of the configuration's
``head_dim`` (not hidden / heads); RMSNorm over each head's features of
q and k, then rotary positions; attention in which key ``j`` is visible
to query ``i`` iff ``j // L <= i // L`` (block-causal, blocks aligned to
position 0); pre-RMSNorm; a router whose softmax over ALL experts picks
the ``top_k`` largest, renormalised over those (``norm_topk_prob``);
every expert's SwiGLU computed for every token and weighted (zero
outside the ``top_k``): no capacity, no dropped token, no shared expert.
Final RMSNorm, untied head.

``generate`` is the released ``generate.py`` procedure at temperature 0
with ``remasking="low_confidence_static"``: the prompt's whole blocks are
context, what is left over opens the first block unmasked; a block of
``L`` positions opens masked; every denoising forward is a FULL forward
over context + block, takes at each masked position the arg-max token
and its softmax probability, and unmasks the ``n_t`` most probable, where
a block that opened with ``M`` masked positions and takes ``S`` steps has
``n_t = M // S`` (+1 for the first ``M mod S`` steps); once none is
masked the block is committed and the next opens. A position predicts
its own token (no shift).

Departures from the published code, each noted:
- the mask token's id is an argument (the config has no key for it; the
  tokenizer's ``<|MASK|>`` is 151669 as far as is known here). Which
  positions are masked is state here, never read off the ids;
- the rotation pairs neighbouring features ``(2i, 2i+1)`` as the program
  does; the Hugging Face port pairs ``(i, i + d/2)`` and permutes the
  q/k weights to match. With seeded random weights the two are the same
  model up to that permutation;
- the static schedule only (no dynamic threshold), temperature 0 only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def _f32(w):
    return w.astype(jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


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


def router_weights(m, router, top_k, norm_topk_prob, dtype=jnp.float32):
    """[s, E] weight of every expert for every token: the softmax over
    all experts, kept at the ``top_k`` largest and zero elsewhere,
    renormalised over the kept ones. ``dtype`` is the precision the
    router's product and softmax run in (float32 in the model; the
    planted-fault tests lower it)."""
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(jnp.matmul(m.astype(dtype), router.astype(dtype)),
                           axis=-1).astype(jnp.float32)
    kth = jnp.sort(p, axis=-1)[:, -top_k][:, None]
    kept = jnp.where(p >= kth, p, 0.0)
    if norm_topk_prob:
        kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return kept


def _stored_as(w, dtype):
    """float32 ``w`` [in, out] after a round trip through ``dtype``
    (``int8``, ``float8_e4m3fn``) with one scale an output channel, as
    experts kept in that type would be stored; ``None`` leaves it."""
    w = _f32(w)
    if dtype is None:
        return w
    dtype = jnp.dtype(dtype)
    whole = jnp.issubdtype(dtype, jnp.integer)
    top = float(jnp.iinfo(dtype).max if whole else jnp.finfo(dtype).max)
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / top
    q = w / scale
    return (jnp.round(q) if whole else q).astype(dtype).astype(
        jnp.float32) * scale


def experts(m, gates, w, expert_dtype=None):
    """sum over e of gates[:, e] * W_down,e (silu(W_gate,e m) * W_up,e m),
    every expert computed for every token, one expert at a time so that
    one expert's float32 copy is live."""
    def one_expert(acc, ew):
        wg, wu, wd = (_stored_as(x, expert_dtype) for x in ew[:3])
        h = jax.nn.silu(_mm(m, wg)) * _mm(m, wu)
        return acc + ew[3][:, None] * _mm(h, wd), None

    acc, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (w["gate_proj"], w["up_proj"], w["down_proj"], gates.T))
    return acc


def block(x, w, *, num_heads, num_kv_heads, head_dim, rope_theta, eps,
          top_k, norm_topk_prob, block_length, router_dtype=jnp.float32,
          drop_top=False, expert_dtype=None):
    """One decoder block on x [s, hidden]; w: dict of this block's
    weights. ``router_dtype``, ``drop_top`` (every token loses the
    expert it weighs most, as a full expert under a capacity would drop
    it) and ``expert_dtype`` (the experts' matrices kept in a type below
    the configuration's, ``_stored_as``) plant the faults the tolerances
    have to catch."""
    s = x.shape[0]
    h = _rms_norm(x, _f32(w["input_layernorm"]), eps)
    q = _mm(h, _f32(w["q_proj"])).reshape(s, num_heads, head_dim)
    k = _mm(h, _f32(w["k_proj"])).reshape(s, num_kv_heads, head_dim)
    v = _mm(h, _f32(w["v_proj"])).reshape(s, num_kv_heads, head_dim)
    q = _rope(_rms_norm(q, _f32(w["q_norm"]), eps), rope_theta)
    k = _rope(_rms_norm(k, _f32(w["k_norm"]), eps), rope_theta)
    rep = num_heads // num_kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) \
        / (head_dim ** 0.5)
    blk = jnp.arange(s) // block_length
    visible = blk[None, :] <= blk[:, None]
    probs = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI)
    x = x + _mm(attn.reshape(s, num_heads * head_dim), _f32(w["o_proj"]))
    m = _rms_norm(x, _f32(w["post_attention_layernorm"]), eps)
    gates = router_weights(m, w["router"], top_k, norm_topk_prob,
                           dtype=router_dtype)
    if drop_top:
        gates = jnp.where(gates >= gates.max(-1, keepdims=True), 0.0, gates)
    return x + experts(m, gates, w, expert_dtype)


_experts = jax.jit(experts, static_argnames=("expert_dtype",))
_block = jax.jit(block, static_argnames=(
    "num_heads", "num_kv_heads", "head_dim", "rope_theta", "eps", "top_k",
    "norm_topk_prob", "block_length", "router_dtype", "drop_top",
    "expert_dtype"))


@jax.jit
def _embed(table, ids):
    return _f32(table)[ids]


@jax.jit
def _head(x, norm_w, head_w, eps):
    return _mm(_rms_norm(x, _f32(norm_w), eps), _f32(head_w))


def weights_of(model):
    """(embedding, [per-block dicts], final norm, head) read from a
    ``paddle_tpu.models.SDAR``: parameter arrays only."""
    blocks = []
    for layer in model.layers:
        a, e = layer.self_attn, layer.mlp
        blocks.append({
            "input_layernorm": layer.input_layernorm.weight._data,
            "post_attention_layernorm":
                layer.post_attention_layernorm.weight._data,
            "q_proj": a.q_proj.weight._data, "k_proj": a.k_proj.weight._data,
            "v_proj": a.v_proj.weight._data, "o_proj": a.o_proj.weight._data,
            "q_norm": a.q_norm.weight._data, "k_norm": a.k_norm.weight._data,
            "router": e.router._data, "gate_proj": e.gate_proj._data,
            "up_proj": e.up_proj._data, "down_proj": e.down_proj._data})
    return (model.embed_tokens.weight._data, blocks,
            model.norm.weight._data, model.lm_head.weight._data)


def fields_of(config):
    """The ``fields`` of ``logits`` from a configuration file's keys (the
    Hugging Face names) and the cell's generation settings."""
    return {"num_heads": config["num_attention_heads"],
            "num_kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "rope_theta": config["rope_theta"],
            "rms_norm_eps": config["rms_norm_eps"],
            "top_k": config["num_experts_per_tok"],
            "norm_topk_prob": config["norm_topk_prob"],
            "block_length": config["block_length"]}


def logits(weights, fields, ids, rows=None, **faults):
    """float32 logits [len(ids) or len(rows), vocab] of the full forward
    pass over ``ids`` under the block-causal mask; ``rows`` restricts the
    head to those positions. Blocks run one jitted call each."""
    table, blocks, norm_w, head_w = weights
    with jax.default_matmul_precision("highest"):
        x = _embed(table, jnp.asarray(ids, jnp.int32))
        for w in blocks:
            x = _block(x, w, num_heads=int(fields["num_heads"]),
                       num_kv_heads=int(fields["num_kv_heads"]),
                       head_dim=int(fields["head_dim"]),
                       rope_theta=float(fields["rope_theta"]),
                       eps=float(fields["rms_norm_eps"]),
                       top_k=int(fields["top_k"]),
                       norm_topk_prob=bool(fields["norm_topk_prob"]),
                       block_length=int(fields["block_length"]), **faults)
        if rows is not None:
            x = x[jnp.asarray(rows, jnp.int32)]
        return _head(x, norm_w, head_w, jnp.float32(fields["rms_norm_eps"]))


def unmask_counts(masked, steps):
    """Positions each denoising forward of a block unmasks
    (``low_confidence_static``): ``masked // steps``, one more in the
    first ``masked % steps`` forwards; forwards left with none are not
    run."""
    base, extra = divmod(int(masked), int(steps))
    return [n for n in (base + (t < extra) for t in range(int(steps))) if n]


def pick_unmasked(probs, masked, n):
    """The ``n`` masked positions of highest probability, the earlier
    position first among equals: [L] bool."""
    conf = np.where(masked, np.asarray(probs, np.float64), -np.inf)
    pick = np.zeros(len(conf), bool)
    pick[np.argsort(-conf, kind="stable")[:n]] = True
    return pick & np.asarray(masked)


def generate(weights, fields, prompt, max_new_tokens, *, denoise_steps,
             mask_token_id, pad_to=None, on_forward=None):
    """Generate ``max_new_tokens`` after ``prompt``, one full forward a
    step. ``pad_to`` pads every forward to one length (the mask never
    lets a position see a later block, so padding changes nothing before
    it) so that the reference compiles once. ``on_forward(record)`` gets,
    for every forward, ``seq_len``, the block's ``ids`` and ``masked``
    before it, its ``logits`` [L, vocab] and whether it was the
    ``commit``. Returns the generated tokens."""
    width = int(fields["block_length"])
    prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
    context = prompt[:len(prompt) // width * width]
    given = prompt[len(context):]
    out = []
    while len(out) < max_new_tokens:
        ids = given + [int(mask_token_id)] * (width - len(given))
        masked = np.arange(width) >= len(given)
        plan = unmask_counts(masked.sum(), denoise_steps)
        while True:
            seq = np.asarray(context + ids, np.int64)
            if pad_to is not None:
                seq = np.pad(seq, (0, int(pad_to) - len(seq)))
            rows = np.arange(len(context), len(context) + width)
            lg = np.asarray(logits(weights, fields, seq, rows=rows))
            commit = not masked.any()
            if on_forward is not None:
                on_forward({"seq_len": len(context), "ids": list(ids),
                            "masked": masked.copy(), "logits": lg,
                            "commit": commit})
            if commit:
                break
            top = lg.max(-1)
            prob = 1.0 / np.exp(lg - top[:, None]).sum(-1)
            pick = pick_unmasked(prob, masked, plan.pop(0))
            for i in np.flatnonzero(pick):
                ids[i] = int(lg[i].argmax())
            masked &= ~pick
        out.extend(ids[len(given):])
        context, given = context + ids, []
    return out[:max_new_tokens]


# -- the comparison that decides ``correct`` (drivers/serve_blockdiff.py) --
#
# The program computes in bfloat16 (2^-9 a rounding) through 6 layers of
# attention and experts and reads its logits off a float32 accumulator;
# the reference is float32 throughout. Each limit lies between what the
# program reads over its seeds and what the reference made to get
# something wrong reads on the same records, with room on both sides.
# Readings on the chip at the published widths and at the timed load (my
# chip runs, PR 28, PERF.md section 6: 30 recorded forwards a run, 120
# positions, 6 x 120 rows of the expert layers):
#
# logit_rms  root mean square, over a run's positions, of |program's logit
#     of its chosen token - reference's logit of that token| as a share
#     of the forward's max |reference logit| (bfloat16 through 6 layers).
#     Program 0.0019-0.0038; every token's heaviest expert dropped
#     0.020-0.037.
# logit      the same error at its worst position: program 0.0059-0.0179;
#     an expert dropped 0.051-0.079.
# prob       |program's probability - reference's softmax probability of
#     the chosen token| at its worst position, as a share of the
#     reference's (seeded random weights give near-uniform predictions, a
#     probability of ~1e-4, so an absolute limit would hold nothing).
#     Program 0.025-0.082; an expert dropped 0.21-0.38.
# router     |program's weight of an expert - reference's|, largest over
#     experts and rows, the float32 router fed the program's OWN input,
#     both read out of the timed block steps. The logits cannot hold the
#     router to float32: the program's bfloat16 activations move the
#     router's input by as much as a bfloat16 router would move its
#     output (it reads 0.0028-0.0041 on logit_rms). On the same input a
#     float32 softmax over 128 experts agrees to 9e-8 and a bfloat16 one
#     swaps an expert (0.095-0.103).
# experts    root mean square of (program's expert-layer output -
#     reference's experts on the program's own input under its own
#     routing) as a share of the reference's: the grouped matmul, its
#     sort and its scatter with nothing in between. The logits cannot
#     hold the experts' precision either: experts kept in int8 (one scale
#     an output channel) read 0.0026-0.0038 on logit_rms, float8_e4m3fn
#     0.0035-0.0057, inside the program's own error. Here: PROGRAM_F
#
# The margin of the chosen token (reference's maximum less its logit of
# that token) is read and not limited: with near-uniform predictions
# whether an arg-max flips hangs on near-ties, and a sound run reads
# 0-0.016 where an expert dropped reads 0.020-0.086 (root mean square
# 0-0.0020 against 0.0025-0.031): no limit lies between.
LIMITS = {"logit_rms": 2.0 ** -7, "logit": 2.0 ** -5, "prob": 2.0 ** -3,
          "router": 2.0 ** -14, "experts": 2.0 ** -7}
KINDS = ("logit", "margin", "prob")
# a row whose 8th and 9th probabilities lie closer than this share of
# them is a tie no float32 rounding order resolves: left out of (e)
_ROUTER_TIE = 1e-4


def compare_forward(record, ref_logits):
    """One recorded slot-forward of the engine (``Scheduler.
    block_observer``) against the reference's logits [L, vocab] of the
    same ids. Returns, for each of the block's positions [L]: the logit
    error and the margin deficit as shares of the logits' scale, the
    probability error as a share of the reference's."""
    ref = np.asarray(ref_logits, np.float64)
    tok = np.asarray(record["tokens"], np.int64)
    rows = np.arange(len(tok))
    scale = float(np.abs(ref).max())
    ref_at = ref[rows, tok]
    top = ref.max(-1)
    ref_prob = np.exp(ref_at - top) / np.exp(ref - top[:, None]).sum(-1)
    return {"logit": np.abs(np.asarray(record["logits"], np.float64)
                            - ref_at) / scale,
            "margin": (top - ref_at) / scale,
            "prob": np.abs(np.asarray(record["probs"], np.float64)
                           - ref_prob) / ref_prob}


def readings(errors):
    """Of a run's errors (``KINDS`` -> one value a position, as
    ``compare_forward`` gives them, gathered over its forwards): the
    largest of each kind and, as ``<kind>_rms``, its root mean square."""
    out = {}
    for kind in KINDS:
        v = np.asarray(errors[kind], np.float64)
        out[kind] = float(v.max())
        out[kind + "_rms"] = float(np.sqrt(np.mean(np.square(v))))
    return out


def compare_router(weights, experts, m, router, top_k, norm_topk_prob,
                   **fault):
    """(e): the program's router output on ``m`` [T, hidden] (``weights``
    [T, k] float32 and ``experts`` [T, k], as ``distributed.moe.
    route_topk`` gives them) against :func:`router_weights` on the same
    ``m``. Returns the largest difference of an expert's weight over the
    rows that are no tie (``_ROUTER_TIE``), and how many rows were."""
    ref = np.asarray(router_weights(jnp.asarray(m), router, top_k,
                                    norm_topk_prob, **fault), np.float64)
    with jax.default_matmul_precision("highest"):
        p = np.sort(np.asarray(jax.nn.softmax(jnp.matmul(
            jnp.asarray(m, jnp.float32), _f32(router)), axis=-1),
            np.float64), axis=-1)
    decided = (p[:, -top_k] - p[:, -top_k - 1]) > _ROUTER_TIE * p[:, -top_k]
    t = ref.shape[0]
    dense = np.zeros_like(ref)
    dense[np.arange(t)[:, None], np.asarray(experts)] = \
        np.asarray(weights, np.float64)
    return float(np.abs(dense - ref)[decided].max()), int(decided.sum())


def compare_experts(y, m, weights, experts_of, w, drop_top=False,
                    expert_dtype=None):
    """(f): the program's expert layer on ``m`` [T, hidden] (its output
    ``y`` [T, hidden], and the router's ``weights`` and ``experts_of``
    [T, k] it was computed under) against :func:`experts` on the same
    ``m`` under the same routing: the grouped matmul, its sort and its
    scatter with nothing else in between. ``w``: the block's weights
    (``weights_of``); ``drop_top`` and ``expert_dtype`` plant
    :func:`block`'s faults in the reference. Returns the root mean
    square of the difference as a share of the root mean square of the
    reference's output."""
    t = np.asarray(m).shape[0]
    gates = np.zeros((t, w["router"].shape[1]), np.float32)
    gates[np.arange(t)[:, None], np.asarray(experts_of)] = \
        np.asarray(weights, np.float32)
    if drop_top:
        gates = np.where(gates >= gates.max(-1, keepdims=True), 0.0, gates)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(_experts(_f32(jnp.asarray(m)), jnp.asarray(gates),
                                  w, expert_dtype=expert_dtype), np.float64)
    diff = np.asarray(jnp.asarray(y, jnp.float32), np.float64) - ref
    return float(np.sqrt(np.mean(diff * diff) / np.mean(ref * ref)))

