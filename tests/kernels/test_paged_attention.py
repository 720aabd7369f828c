"""Pallas paged-decode attention kernel vs the dense XLA reference.

Reference capability: paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu (+ masked_multihead_attention_kernel.cu)
— the paged KV-cache decode path. The kernel (kernels/pallas/
paged_attention.py) gathers pages in-kernel via scalar-prefetched block
tables; here it runs in interpret mode against
`paged_decode_attention_dense`.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.inference.paged import paged_decode_attention_dense
from paddle_tpu.kernels.pallas.paged_attention import (
    fold_block_rows, paged_decode_attention_chunked,
    paged_decode_attention_kernel, unfold_block_rows)


def _case(B, HQ, HK, D, BS, MBPS, lens, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    NB = B * MBPS + 1
    kp = jnp.asarray(rng.randn(NB, BS, HK, D), dtype)
    vp = jnp.asarray(rng.randn(NB, BS, HK, D), dtype)
    q = jnp.asarray(rng.randn(B, HQ, D), dtype)
    tbl = np.zeros((B, MBPS), np.int32)
    for i in range(B):
        need = int(np.ceil(lens[i] / BS)) if lens[i] else 0
        tbl[i, :need] = rng.permutation(np.arange(
            1 + i * MBPS, 1 + i * MBPS + MBPS))[:need]  # scattered blocks
    return q, kp, vp, jnp.asarray(tbl), jnp.asarray(
        np.asarray(lens, np.int32))


# a page a step, and chunks of 3 pages (no table below is a multiple of
# it, so every slot ends in a chunk that is part dead)
_KERNELS = {
    "page": lambda *a, **kw: paged_decode_attention_kernel(
        *a, interpret=True, **kw),
    "chunked": lambda *a, **kw: paged_decode_attention_chunked(
        *a, interpret=True, chunk_pages=3, **kw),
}


@pytest.mark.parametrize("kernel", sorted(_KERNELS))
@pytest.mark.parametrize(
    "B,HQ,HK,D,BS,MBPS,lens",
    [
        (2, 8, 8, 64, 16, 4, [30, 64]),       # MHA
        (3, 8, 2, 128, 16, 8, [1, 100, 128]),  # GQA group 4
        (2, 4, 1, 64, 32, 4, [5, 0]),          # MQA + inactive slot
        (1, 16, 8, 128, 16, 16, [250]),        # long context
        (4, 8, 4, 64, 64, 4, [200, 64, 65, 17]),  # large pages
        (5, 8, 2, 128, 16, 8, [0, 0, 47, 0, 128]),  # idle slots between
    ],
)
def test_kernel_matches_dense(B, HQ, HK, D, BS, MBPS, lens, kernel):
    q, kp, vp, tbl, sl = _case(B, HQ, HK, D, BS, MBPS, lens)
    dense = paged_decode_attention_dense(q, kp, vp, tbl, sl)
    kern = _KERNELS[kernel](q, kp, vp, tbl, sl)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               atol=5e-5, rtol=1e-4)


@pytest.mark.parametrize("kernel", sorted(_KERNELS))
def test_kernel_bf16(kernel):
    q, kp, vp, tbl, sl = _case(2, 8, 4, 128, 16, 4, [17, 33],
                               dtype=jnp.bfloat16)
    dense = paged_decode_attention_dense(q, kp, vp, tbl, sl)
    kern = _KERNELS[kernel](q, kp, vp, tbl, sl)
    np.testing.assert_allclose(
        np.asarray(kern, np.float32), np.asarray(dense, np.float32),
        atol=3e-2, rtol=3e-2)


def test_fold_block_rows_round_trip():
    """A block of L = 4 query rows a slot joins the GQA group and comes
    back: fold and unfold are inverses, a KV head's L x g rows are
    contiguous, and the kernel on the folded rows is the dense reference
    row by row."""
    B, L, HQ, HK, D = 2, 4, 8, 2, 128
    q, kp, vp, tbl, sl = _case(B, HQ, HK, D, 16, 8, [37, 128])
    q4 = jnp.asarray(np.random.RandomState(1).randn(B, L, HQ, D),
                     jnp.float32)
    folded = fold_block_rows(q4, HK)
    assert folded.shape == (B, L * HQ, D)
    np.testing.assert_array_equal(
        np.asarray(unfold_block_rows(folded, L, HK)), np.asarray(q4))
    g = HQ // HK
    # row (h, l, j) of the fold is query head h * g + j of block row l
    np.testing.assert_array_equal(
        np.asarray(folded).reshape(B, HK, L, g, D)[:, 1, 2, 0],
        np.asarray(q4)[:, 2, g])
    got = unfold_block_rows(paged_decode_attention_chunked(
        folded, kp, vp, tbl, sl, interpret=True, name="paged_block"),
        L, HK)
    for l in range(L):
        np.testing.assert_allclose(
            np.asarray(got[:, l]),
            np.asarray(paged_decode_attention_dense(q4[:, l], kp, vp,
                                                    tbl, sl)),
            atol=5e-5, rtol=1e-4)


def test_kernel_custom_scale():
    q, kp, vp, tbl, sl = _case(2, 8, 8, 64, 16, 4, [30, 64])
    dense = paged_decode_attention_dense(q, kp, vp, tbl, sl, scale=0.5)
    kern = paged_decode_attention_kernel(q, kp, vp, tbl, sl, scale=0.5,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               atol=5e-5, rtol=1e-4)


def test_kernel_single_token_seq():
    """seq_len=1: exactly one valid position, first page only."""
    q, kp, vp, tbl, sl = _case(1, 4, 4, 64, 16, 2, [1])
    dense = paged_decode_attention_dense(q, kp, vp, tbl, sl)
    kern = paged_decode_attention_kernel(q, kp, vp, tbl, sl,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               atol=5e-5, rtol=1e-4)
