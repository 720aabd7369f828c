"""Scaled-dot-product / flash attention.

Capability parity with the reference's `flash_attn_kernel.cu:128` (FA2
dynload) and `python/paddle/nn/functional/flash_attention.py`. Two paths:

- `sdpa_xla`: straight jnp attention — the numeric oracle, the CPU
  backend's route, and the route for masks/dropout the kernel lacks.
- Pallas TPU kernel (`paddle_tpu/kernels/pallas/flash_attention.py`), used
  automatically on TPU for supported shapes/dtypes.

Layout is paddle's: [batch, seq, num_heads, head_dim].
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.dispatch import apply, unwrap
from ..core.random import next_key
from . import on_mesh


def _use_pallas(q) -> bool:
    import os

    force = os.environ.get("PADDLE_FLASH_FORCE")  # A/B switch: pallas|xla
    if force == "xla":
        return False
    if jax.default_backend() == "cpu":
        return force == "pallas"
    # MXU-friendly: head_dim multiple of 128 handled by kernel padding; seq
    # must be tile-divisible. The pallas kernel pads internally; gate only on
    # dtype support.
    return q.dtype in (jnp.float32, jnp.bfloat16)


def sdpa_xla(q, k, v, bias=None, causal=False, scale=None):
    """Reference attention on [B, S, H, D] arrays (not Tensors)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # fp32 logits for stability (matches FA2 semantics)
    logits = jnp.einsum("bsnd,btnd->bnst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
        logits = jnp.where(mask, logits, -jnp.inf)
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", probs, v)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(query, key, value, attn_mask=None,
                                       dropout_p=dropout, is_causal=causal,
                                       training=training)
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen (packed ragged-batch) flash attention, parity with the
    reference `flash_attn_unpadded` (`flash_attn_kernel.cu:128`
    flash_attn_varlen_fwd): q/k/v are [total_tokens, heads, dim] with
    cu_seqlens prefix sums. TPU path: segment-ids Pallas kernel; CPU/mask
    fallback computes per-segment masked attention."""
    cu_q = unwrap(cu_seqlens_q)
    cu_k = unwrap(cu_seqlens_k)

    def _varlen(q, k, v):
        from .pallas.flash_attention import flash_attn_varlen
        out = flash_attn_varlen(q, k, v, cu_q, cu_k, causal=causal,
                                scale=scale)
        if training and dropout > 0.0:
            keep = jax.random.bernoulli(next_key(), 1.0 - dropout, out.shape)
            out = jnp.where(keep, out / (1.0 - dropout), 0.0)
        return out.astype(q.dtype)
    return apply(_varlen, query, key, value, name="flash_attn_unpadded"), None


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """SDPA on Tensors of shape [batch, seq, heads, head_dim] (paddle
    layout). GQA supported: key/value may have fewer heads (must divide)."""
    mask_arr = unwrap(attn_mask)
    use_dropout = training and dropout_p > 0.0
    key_rng = next_key() if use_dropout else None
    # Route decision OUTSIDE the traced closure: _use_pallas reads the
    # PADDLE_FLASH_FORCE env A/B switch, and anything read inside the
    # closure is invisible to the dispatch-cache key — flipping the env
    # var would silently cache-hit the other path's trace. As a closure
    # cell (bool) it is part of _fn_key.
    route_pallas = (_use_pallas(unwrap(query)) and mask_arr is None
                    and not use_dropout)
    # the declared device mesh rides as a closure cell for the same
    # reason: a model first served on one chip and then on four must not
    # cache-hit the unsharded trace
    mesh = on_mesh.current() if route_pallas else None

    def _sdpa(q, k, v):
        if route_pallas:
            # native-GQA Pallas kernel: grouped KV heads are never expanded
            from .pallas.flash_attention import (
                flash_attention as pallas_flash)
            return pallas_flash(q, k, v, causal=is_causal, on_mesh=mesh)
        qh, kh = q.shape[2], k.shape[2]
        if kh != qh:  # GQA on the XLA fallback path: repeat kv heads
            rep = qh // kh
            k2 = jnp.repeat(k, rep, axis=2)
            v2 = jnp.repeat(v, rep, axis=2)
        else:
            k2, v2 = k, v
        bias = None
        if mask_arr is not None:
            m = mask_arr
            if m.dtype == jnp.bool_:
                bias = jnp.where(m, 0.0, -jnp.inf)
            else:
                bias = m
        out = sdpa_xla(q, k2, v2, bias=bias, causal=is_causal)
        if use_dropout:
            keep = jax.random.bernoulli(key_rng, 1.0 - dropout_p, out.shape)
            out = jnp.where(keep, out / (1.0 - dropout_p), 0.0)
        return out.astype(q.dtype)
    return apply(_sdpa, query, key, value, name="flash_attention")
