"""Original TPU paged-decode attention kernel (Pallas).

Capability parity with the reference's hand-fused paged decode path
(`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu:1` —
block tables over a shared KV pool — and
`masked_multihead_attention_kernel.cu` — single-token masked decode).

TPU-native design, not a CUDA translation:

- **Block tables ride scalar prefetch** (`pltpu.PrefetchScalarGridSpec`)
  and the pools stay in HBM: the grid walks the slots, and each slot's
  *live* pages are copied in-kernel, by DMAs the body itself starts from
  the prefetched table, into VMEM — the gathered KV is never
  materialized in HBM (the dense fallback's `pool[tables]` materializes
  the whole padded [B, S_max, Hk, D] copy before attending; this kernel
  reads each live page exactly once). A dead page (beyond a slot's
  seq_len) costs nothing: no grid step, no index, no copy. (Until PR 29
  every page of the table was a BlockSpec input of a (slot, chunk) grid:
  on the v5e the pipeline's bookkeeping for 128 pages x 2 pools a slot,
  dead or live, was 320 us of a 385 us call, PERF.md section 6.)
- **Dense pages**: a pool goes in as [NB, bs * Hk, D] (the same bytes as
  [NB, bs, Hk, D]), so a page is one contiguous DMA and a VMEM tile with
  nothing padded; row ``r`` is token ``r // Hk``, KV head ``r % Hk``.
- **A chunk of pages is ONE KV tile** (`chunk_pages` pages,
  `pick_chunk_pages`' static pick): every query row of the slot meets
  the whole [chunk_pages * bs * Hk, D] tile in one matmul, under a mask
  that keeps, for a row, the columns of its own KV head below seq_len —
  so `p x V` sums each row over its own head only, with no per-head
  slice or relayout. That is Hk times the useful FLOPs and still far
  under the memory time; the MXU sees [Hq, D] x [D, thousands] instead
  of [group, D] x [D, bs] a page and a head.
- **One online-softmax update a chunk** for all heads: running (m, l) and
  an f32 accumulator in VMEM scratch, the flash-attention-2 recurrence of
  the training kernel (`flash_attention.py`) once per chunk_pages * bs
  keys, not once a page and a head.
- **Two buffers, filled ahead**: while a chunk is computed the slot's
  next chunk — or the first chunk of the next slot that has keys — is
  already on its way, so a slot's first page is not waited for either.
- **GQA group-fold**: q rows are [group, D] per KV head, a KV head's rows
  contiguous; KV heads are never expanded. A block of L query rows a
  slot joins the group (`fold_block_rows`).
- **Dequant fusion** (the int8 KV tier, FLAGS_kv_cache_dtype): int8
  pools ride the same copies with their per-(token, kv-head) fp32 scales
  as lane-dense rows, and dequantize on the score side, in f32: `(q .
  k_int8) * k_scale` and `(p * v_scale) . v_int8` are the reference's
  `q . (k_int8 * k_scale)` and `p . (v_int8 * v_scale)` with the scales
  never rounded to the compute dtype — gather + dequant + attention in
  one pass, no dequantized page ever returning to HBM (the dense path's
  `_gather_kv` materializes the whole dequantized [B, S_max, Hk, D]
  copy).
- **One body**: `paged_decode_attention_kernel` (short tables) is the
  chunked call at one page a chunk; both are one `pallas_call`, one
  device operation under the caller's name.

Decode attention is HBM-bandwidth-bound: the win over the dense path is
touching only live pages, once. On the v5e a call of the Mistral-7B cell
(32 slots, 13.5 k context tokens, 16-token pages) takes 98 us, 69 % of
what its bytes allow (PERF.md, PR 29).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret

__all__ = ["paged_decode_attention_kernel",
           "paged_decode_attention_chunked", "pick_chunk_pages",
           "fold_block_rows", "unfold_block_rows"]

# f32/i32-typed literals: under jax_enable_x64 bare python numbers trace as
# weak 64-bit constants that Mosaic cannot legalize (see flash_attention.py)
_NEG = np.float32(-1e30)
_ZERO = np.float32(0.0)
_ONE = np.float32(1.0)
_I0 = np.int32(0)


def _decode_kernel(tables_ref, lens_ref, q_ref, *refs, hk, g, bs, cpp,
                   scale, quantized):
    """The one body of every paged kernel, a slot a grid step. The
    slot's live pages come ``cpp`` at a time by DMA from the pools (which
    stay in HBM) into one of two [cpp, bs * Hk, D] buffers — the next
    chunk's, or the next live slot's first, start before this chunk is
    waited for — and each chunk is ONE KV tile: one matmul of every query
    row against it and one online-softmax update of the whole (m, l,
    acc) scratch. A dead page costs nothing: it is neither fetched nor
    visited. Row ``r`` of the tile is token ``r // Hk``, KV head
    ``r % Hk``; a score column belongs to a query row iff it is that
    row's KV head and its token is below ``seq_len`` — which also removes
    what a buffer still holds past the chunk's live pages."""
    n = 4 if quantized else 2               # K, V[, their scale rows]
    srcs, o_ref, dsts = refs[:n], refs[n], refs[n + 1:2 * n + 1]
    sem, parity, acc, m_scr, l_scr = refs[2 * n + 1:]
    moved = tuple(zip(srcs, dsts))
    kbuf, vbuf, *scale_bufs = dsts
    b = pl.program_id(0)
    nslots, npages = tables_ref.shape
    rows, d = q_ref.shape[1:]
    cols = cpp * bs * hk
    i32 = np.int32
    bs_i, cpp_i, hk_i = i32(bs), i32(cpp), i32(hk)
    last_slot, one = i32(nslots - 1), i32(1)

    def live_pages(slot, chunk):
        need = jnp.minimum((lens_ref[slot] + bs_i - one) // bs_i,
                           i32(npages))
        return jnp.clip(need - chunk * cpp_i, _I0, cpp_i)

    def chunk_dma(slot, chunk, buf, start):
        """Start, or wait for, the copies of a chunk's live pages."""
        def page(j, carry):
            block = tables_ref[slot, chunk * cpp_i + j]
            for src, dst in moved:
                copy = pltpu.make_async_copy(
                    src.at[block], dst.at[buf, j], sem.at[buf])
                copy.start() if start else copy.wait()
            return carry

        jax.lax.fori_loop(_I0, live_pages(slot, chunk), page, _I0)

    def fetch_first_of_next_live(after, buf):
        nxt = jax.lax.while_loop(
            lambda s: jnp.logical_and(
                s < i32(nslots),
                lens_ref[jnp.minimum(s, last_slot)] <= _I0),
            lambda s: s + one, after)

        @pl.when(nxt < i32(nslots))
        def _():
            chunk_dma(nxt, _I0, buf, True)

    @pl.when(b == 0)
    def _prologue():
        # what a buffer holds past a chunk's live pages is masked, not
        # multiplied away: it has to be finite from the start
        for _, dst in moved:
            dst[...] = jnp.zeros_like(dst)
        parity[0] = _I0
        fetch_first_of_next_live(_I0, _I0)

    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, _NEG)
    l_scr[:] = jnp.zeros_like(l_scr)
    chunk_tokens = i32(cpp * bs)
    seq_len = jnp.minimum(lens_ref[b], i32(npages * bs))
    nchunks = (seq_len + chunk_tokens - one) // chunk_tokens
    first_buf = parity[0]
    dtype = q_ref.dtype
    # one compare does the head mask and the length mask: a column's key
    # is its KV head, or -1 past the slot's length
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    col_token, col_head = col // hk_i, col % hk_i
    row_head = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // i32(g)

    def tile(buf_ref, buf):
        blk = buf_ref[buf]                          # [cpp, bs * Hk, D]
        if blk.dtype != dtype:
            blk = blk.astype(jnp.float32).astype(dtype)
        return blk.reshape(cols, d)

    def scale_row(buf_ref, buf):                    # [1, cols], lane-dense
        # a page's row came padded to whole 128-lane tiles (what a DMA
        # moves); its bs * Hk scales are the row's head
        return jnp.concatenate(
            [buf_ref[buf, j][:, :bs * hk] for j in range(cpp)], axis=1)

    def chunk_update(i, carry):
        buf = (first_buf + i) % i32(2)

        # the next chunk's pages, or the next live slot's first, are on
        # their way while this chunk is computed
        @pl.when(i + one < nchunks)
        def _():
            chunk_dma(b, i + one, one - buf, True)

        @pl.when(i + one == nchunks)
        def _():
            fetch_first_of_next_live(b + one, one - buf)

        chunk_dma(b, i, buf, False)
        k, v = tile(kbuf, buf), tile(vbuf, buf)
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [rows, cols]
        if quantized:
            # dequant on the score side: (q . k_int8) * k_scale is
            # q . (k_int8 * k_scale) with the scale kept in f32
            s = s * scale_row(scale_bufs[0], buf)
        col_key = jnp.where(i * chunk_tokens + col_token < seq_len,
                            col_head, i32(-1))
        s = jnp.where(col_key == row_head, s, _NEG)
        m_prev = m_scr[:, :1]                       # [rows, 1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a live chunk holds a live token, so every row has a live column
        # and m_new is finite: exp() alone takes masked columns to 0
        pmat = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pmat, axis=-1, keepdims=True)
        if quantized:
            pmat = pmat * scale_row(scale_bufs[1], buf)
        acc[:] = acc[:] * alpha + jax.lax.dot(
            pmat.astype(dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(_I0, nchunks, chunk_update, _I0)
    parity[0] = (first_buf + nchunks) % i32(2)
    l = l_scr[:, :1]                            # 0 where no key: zeros
    o_ref[0] = (acc[:] / jnp.where(l > _ZERO, l, _ONE)).astype(o_ref.dtype)


def _paged_call(q, k_pool, v_pool, block_tables, seq_lens, scale,
                interpret, k_scale, v_scale, cpp, name):
    """``cpp`` pages a chunk through :func:`_decode_kernel`. The pools go
    in as [NB, bs * Hk, D], so a page is a dense block with nothing
    padded. That view is the same bytes, and no copy, where a token's
    heads fill whole tiles of the chip's layout (head size a multiple of
    128 and 1, 2, 4, 8... KV heads; int8 from 4): at other shapes the
    compiler copies the pool in front of the call, as it did in front of
    the [bs, Hk, D] blocks this replaced; a head size that is no
    multiple of 128 is such a shape, and is padded to one there. The
    scales go in as [NB, 1, bs * Hk], lane-dense rows (a small copy a
    call: the cache keeps them [NB, bs, Hk])."""
    b, hq, d = q.shape
    nb, bs, hk, _ = k_pool.shape
    sm_scale = np.float32(scale if scale is not None
                          else 1.0 / math.sqrt(d))
    quantized = k_scale is not None
    if interpret is None:
        interpret = _interpret()
    cpp = max(min(int(cpp), block_tables.shape[1]), 1)
    page = bs * hk

    def lanes(x):
        """A DMA moves whole 128-lane tiles: pad the last dimension to
        them (zeros: they add nothing to a score, and give output
        columns that are cut off again)."""
        pad = -x.shape[-1] % 128
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad \
            else x

    ins = [lanes(q), lanes(k_pool.reshape(nb, page, d)),
           lanes(v_pool.reshape(nb, page, d))]
    dp = ins[0].shape[-1]
    buffers = [pltpu.VMEM((2, cpp, page, dp), k_pool.dtype)] * 2
    if quantized:
        ins += [lanes(k_scale.astype(jnp.float32).reshape(nb, 1, page)),
                lanes(v_scale.astype(jnp.float32).reshape(nb, 1, page))]
        buffers += [pltpu.VMEM((2, cpp) + ins[-1].shape[1:],
                               jnp.float32)] * 2
    q_spec = pl.BlockSpec((1, hq, dp),
                          lambda bb, tbl, lens: (bb, _I0, _I0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[q_spec] + [in_hbm] * (len(ins) - 1),
        out_specs=q_spec,
        scratch_shapes=buffers + [
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hq, dp), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, hk=hk, g=hq // hk, bs=bs,
                               cpp=cpp, scale=sm_scale,
                               quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(ins[0].shape, q.dtype),
        interpret=interpret,
        name=name + "_q8" if quantized else name,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), *ins)
    return out[..., :d]


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "name"))
def paged_decode_attention_kernel(q, k_pool, v_pool, block_tables,
                                  seq_lens, scale=None, interpret=None,
                                  k_scale=None, v_scale=None,
                                  name="paged_decode"):
    """Decode attention over a paged KV cache, fused in one Pallas kernel,
    one page a chunk (short tables).

    q [B, Hq, D] (one query token per slot); k_pool/v_pool
    [NB, bs, Hk, D]; block_tables [B, MBPS] int32; seq_lens [B] int32.
    Quantized pools pass int8 k_pool/v_pool plus ``k_scale``/``v_scale``
    [NB, bs, Hk] f32 — the page copies then carry the scale rows and the
    scores dequantize in VMEM (dequant fusion). Returns [B, Hq, D]. Matches
    `paged_decode_attention_dense` (the dense reference path, same int8
    pool) closely; tested one-vs-other. ``name`` is the kernel's name in
    a device trace (``fold_block_rows``' callers pass their own).
    """
    return _paged_call(q, k_pool, v_pool, block_tables, seq_lens, scale,
                       interpret, k_scale, v_scale, 1, name)


def fold_block_rows(q, hk):
    """A block of L query rows a slot as the kernels' GQA group: q
    [B, L, Hq, D] -> [B, Hk * (L * g), D], a KV head's L x g rows
    contiguous, so the head mask of ``_decode_kernel`` (row // rows a
    head) folds them as it folds the g rows of a single query. Every
    row then sees the same ``seq_lens[b]`` keys
    (the caller counts the block's own L in): attention with no mask
    inside the block. L = 1 is the identity."""
    b, l, hq, d = q.shape
    g = hq // hk
    return q.reshape(b, l, hk, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, hk * l * g, d)


def unfold_block_rows(out, l, hk):
    """Inverse of :func:`fold_block_rows` on the kernels' output."""
    b, rows, d = out.shape
    hq = rows // l
    g = hq // hk
    return out.reshape(b, hk, l, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, l, hq, d)


# chunk candidates, and what a chunk may hold in VMEM (the default
# scoped limit of a v5e core is 16 MiB; the rest is q, out, the scratch
# and Mosaic's own temporaries)
_CHUNK_CANDIDATES = (2, 4, 8, 16, 32)
_CHUNK_VMEM_BUDGET = 8 * 1024 * 1024


def pick_chunk_pages(npages, bs, hk, d, itemsize=2, rows=None,
                     budget=_CHUNK_VMEM_BUDGET):
    """Autotune-style static chunk-length pick for the chunked
    flash-decode: the largest candidate whose chunk fits the VMEM
    ``budget``, never exceeding the table length. A chunk holds the
    dense K and V pages in the pools' ``itemsize`` twice (the buffer
    being computed on and the one being filled; int8 once more as the
    tile in the compute dtype), and the [``rows``, cpp * bs * Hk] score
    tile in f32 twice (scores, then their exponentials) and once in the
    compute dtype (``rows``: the query rows a slot, default one a KV
    head). Pure shape math — deterministic per configuration, so jit
    cache keys stay stable."""
    rows = int(rows) if rows else hk
    itemsize = max(int(itemsize), 1)
    wide = max(itemsize, 2)                         # int8 computes in bf16
    tile = wide if wide > itemsize else 0           # ... from a copy
    lanes = -(-d // 128) * 128
    best = 1
    for cpp in _CHUNK_CANDIDATES:
        if cpp > max(int(npages), 1):
            break
        keys = cpp * bs * hk
        if (2 * keys * lanes * (2 * itemsize + tile)
                + rows * keys * (4 + 4 + wide)) <= budget:
            best = cpp
    return best


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "chunk_pages", "name"))
def paged_decode_attention_chunked(q, k_pool, v_pool, block_tables,
                                   seq_lens, scale=None, interpret=None,
                                   k_scale=None, v_scale=None,
                                   chunk_pages=None, name="paged_decode"):
    """Chunked flash-decode: :func:`paged_decode_attention_kernel` with
    ``chunk_pages`` pages of a slot's context fetched, matched against
    every query row and folded into the online softmax at a time (long
    contexts stop paying a copy's wait, a matmul's fill and a scratch
    round-trip per page). Same signature/semantics as the per-page
    kernel, full-precision or dequant-fused int8 pools;
    ``chunk_pages=None`` autotunes via :func:`pick_chunk_pages`."""
    _, bs, hk, d = k_pool.shape
    cpp = int(chunk_pages) if chunk_pages else pick_chunk_pages(
        block_tables.shape[1], bs, hk, d,
        jnp.dtype(k_pool.dtype).itemsize, rows=q.shape[1])
    return _paged_call(q, k_pool, v_pool, block_tables, seq_lens, scale,
                       interpret, k_scale, v_scale, cpp, name + "_chunked")
