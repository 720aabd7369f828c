"""In-register dequant int8 weight matmul (Pallas).

The QAT/PTQ deployment forms (`quantization.ConvertedInt8Linear`) keep
weights int8 with per-out-channel fp32 scales, but their forward used
to rebuild the full fp32 weight in XLA (`w_int8 * scales` then matmul)
— the dequantized weight materializes in HBM and v5e's doubled int8
matmul peak never engages. This kernel keeps the weight int8 all the
way into VMEM and dequantizes **in-register** against the per-channel
scale tile right before the MXU contraction, so HBM only ever moves
int8 weight bytes.

Reference capability: the int8 weight-only GEMM epilogue of
`paddle/phi/kernels/fusion/gpu/fused_weight_only_linear` — expressed
TPU-natively: a (M-tile, N-tile) grid with the full K axis resident
per step (serving K = hidden_size, comfortably VMEM-sized), scales
riding a [1, N] row so the dequant is one broadcast multiply.

Numerics match the XLA dequant-then-matmul form exactly in spirit and
bitwise-closely in practice (same f32 contraction,
`preferred_element_type=f32`); tests/framework/test_pallas_kernels.py
pins one-vs-other. Runs under ``interpret=True`` on CPU like the other
serving kernels (`flash_attention._interpret`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .flash_attention import _interpret

__all__ = ["quant_matmul"]

# MXU-friendly tiles; M tiles stay small because serving matmuls are
# token-batch-thin (decode M = batch size)
_BM = 128
_BN = 128
# index-map literal: a bare 0 is i64 under jax_enable_x64, which Mosaic
# cannot legalize (see flash_attention.py)
_I0 = np.int32(0)


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref):
    # dequant in-register: the int8 weight tile meets its [1, BN]
    # per-channel scale row right before the MXU contraction
    w = w_ref[...].astype(jnp.float32) * s_ref[...]
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jax.lax.dot(
        x, w, preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul(x, w_int8, w_scales, interpret=None):
    """``x @ (w_int8 * w_scales)`` with the dequant fused in-kernel.

    x [..., K] float; w_int8 [K, N] int8; w_scales [N] (or [1, N]) f32
    per-out-channel scales. Returns [..., N] in ``x.dtype``. Pads M/N
    up to the tile grid and slices back — K rides whole (serving K =
    hidden size; fits VMEM beside the tiles).
    """
    orig_shape = x.shape
    k, n = w_int8.shape
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    s = w_scales.reshape(1, n).astype(jnp.float32)
    if interpret is None:
        interpret = _interpret()

    bm = min(_BM, max(m, 1))
    bn = min(_BN, n)
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    if mp != m:
        x2 = jnp.pad(x2, ((0, mp - m), (0, 0)))
    w = w_int8
    if np_ != n:
        w = jnp.pad(w, ((0, 0), (0, np_ - n)))
        s = jnp.pad(s, ((0, 0), (0, np_ - n)))

    out = pl.pallas_call(
        _qmm_kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, _I0)),
            pl.BlockSpec((k, bn), lambda i, j: (_I0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (_I0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=interpret,
        name="quant_matmul",
    )(x2, w, s)
    return out[:m, :n].reshape(*orig_shape[:-1], n)
