"""Original TPU paged-decode attention kernel (Pallas).

Capability parity with the reference's hand-fused paged decode path
(`paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu:1` —
block tables over a shared KV pool — and
`masked_multihead_attention_kernel.cu` — single-token masked decode).

TPU-native design, not a CUDA translation:

- **Block tables ride scalar prefetch** (`pltpu.PrefetchScalarGridSpec`):
  the grid walks (slot, page) and each page's pool block is *gathered
  in-kernel* by the BlockSpec index map reading the prefetched table —
  the gathered KV is never materialized in HBM (the dense fallback's
  `pool[tables]` materializes the whole padded [B, S_max, Hk, D] copy
  before attending; this kernel reads each live page exactly once).
- **One whole page per grid step** ([bs, Hk, D] contiguous — a single
  large DMA — rather than per-head slices, which would shred the
  transfer into Hk strided reads).
- **Online softmax across a slot's pages** with running (m, l) and an
  f32 accumulator in VMEM scratch, finalized on the last page — the
  same flash-attention-2 recurrence as the training kernel
  (`flash_attention.py`), specialized to a single query token.
- **GQA group-fold**: q rows are [group, D] per KV head; KV heads are
  never expanded. Dead pages (beyond a slot's seq_len) revisit the null
  block 0, so the pipeline skips the refetch and `pl.when` skips the
  compute.
- **Dequant fusion** (the int8 KV tier, FLAGS_kv_cache_dtype): int8
  pools ride the same in-kernel gather with their per-(slot, kv-head)
  fp32 scale rows as two more scalar-prefetch-indexed block inputs, and
  each page dequantizes IN VMEM (`int8 -> f32 * scale -> compute
  dtype`, exactly `quantization.dequantize_rows`) before the online
  softmax — gather + dequant + attention in one pass, no dequantized
  page ever returning to HBM (the dense path's `_gather_kv`
  materializes the whole dequantized [B, S_max, Hk, D] copy).
- **Chunked flash-decode** (`paged_decode_attention_chunked`): long
  contexts tile the KV sequence axis `chunk_pages` pages per grid step
  (statically unrolled in-kernel) instead of one, amortizing grid/
  scratch overhead over a larger KV tile; `pick_chunk_pages` makes the
  autotune-style static pick — the largest candidate whose K+V tile
  fits a VMEM budget.

Decode attention is HBM-bandwidth-bound: the win over the dense path is
touching only live pages, once. Larger cache page sizes (>= 64) give
longer contiguous DMAs; the cache default block_size=16 works but 64+ is
recommended for TPU serving.
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


def _page_update(q_ref, k_blk, v_blk, acc, m_scr, l_scr, valid, *,
                 hk, g, scale):
    """One page's flash-attention-2 online-softmax update against the
    running (m, l, acc) scratch — shared by the per-page, quantized and
    chunked kernel bodies. ``k_blk``/``v_blk`` are [bs, Hk, D] VMEM
    values (already dequantized for int8 pools); ``valid`` [1, bs]."""
    for h in range(hk):                             # static unroll
        rows = slice(h * g, (h + 1) * g)
        q_h = q_ref[0, rows]                        # [g, D]
        k_h = k_blk[:, h, :]                        # [bs, D]
        v_h = v_blk[:, h, :]
        s = jax.lax.dot_general(
            q_h, k_h, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [g, bs]
        s = jnp.where(valid, s, _NEG)
        m_prev = m_scr[rows, :1]                    # [g, 1]
        l_prev = l_scr[rows, :1]
        m_new = jnp.maximum(m_prev,
                            jnp.max(s, axis=-1, keepdims=True))
        pmat = jnp.where(valid, jnp.exp(s - m_new), _ZERO)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pmat, axis=-1,
                                         keepdims=True)
        acc[rows] = acc[rows] * alpha + jax.lax.dot(
            pmat.astype(v_h.dtype), v_h,
            preferred_element_type=jnp.float32)
        m_scr[rows] = jnp.broadcast_to(m_new, (g, m_scr.shape[1]))
        l_scr[rows] = jnp.broadcast_to(l_new, (g, l_scr.shape[1]))


def _deq(blk, scale_row, dtype):
    """In-VMEM page dequant: the `quantization.dequantize_rows` formula
    (int8 -> f32 * per-(slot, kv-head) scale -> compute dtype), applied
    to one gathered [bs, Hk, D] page so the fused path matches the
    dense reference's `_gather_kv` numerics exactly."""
    return (blk.astype(jnp.float32)
            * scale_row[..., None]).astype(dtype)


def _init_scratch(acc, m_scr, l_scr):
    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, _NEG)
    l_scr[:] = jnp.zeros_like(l_scr)


def _finalize_out(o_ref, acc, l_scr):
    l = l_scr[:, :1]
    safe_l = jnp.where(l > _ZERO, l, _ONE)
    o_ref[0] = (acc[:] / safe_l).astype(o_ref.dtype)


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   acc, m_scr, l_scr, *, hk, g, bs, npages, scale):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        _init_scratch(acc, m_scr, l_scr)

    seq_len = lens_ref[b]

    @pl.when(p * bs < seq_len)
    def _body():
        pos = p * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        valid = pos < seq_len                       # [1, bs]
        _page_update(q_ref, k_ref[0], v_ref[0], acc, m_scr, l_scr,
                     valid, hk=hk, g=g, scale=scale)

    @pl.when(p == npages - 1)
    def _finalize():
        _finalize_out(o_ref, acc, l_scr)


def _decode_kernel_q(tables_ref, lens_ref, q_ref, k_ref, v_ref, ks_ref,
                     vs_ref, o_ref, acc, m_scr, l_scr, *, hk, g, bs,
                     npages, scale):
    """Dequant-fused twin of :func:`_decode_kernel`: the page's int8
    K/V blocks and their [bs, Hk] scale rows arrive through the same
    scalar-prefetched table gather and dequantize in VMEM right before
    the online-softmax update — one pass, no HBM round-trip."""
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        _init_scratch(acc, m_scr, l_scr)

    seq_len = lens_ref[b]

    @pl.when(p * bs < seq_len)
    def _body():
        pos = p * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        valid = pos < seq_len                       # [1, bs]
        k_blk = _deq(k_ref[0], ks_ref[0], q_ref.dtype)
        v_blk = _deq(v_ref[0], vs_ref[0], q_ref.dtype)
        _page_update(q_ref, k_blk, v_blk, acc, m_scr, l_scr, valid,
                     hk=hk, g=g, scale=scale)

    @pl.when(p == npages - 1)
    def _finalize():
        _finalize_out(o_ref, acc, l_scr)


def _decode_kernel_chunked(tables_ref, lens_ref, q_ref, *refs, hk, g,
                           bs, cpp, nchunks, scale, quantized):
    """Chunked flash-decode body: ``cpp`` pages per grid step, each
    statically unrolled through the same online-softmax update (with
    in-VMEM dequant when ``quantized``). Dead pages inside a chunk
    (past seq_len, or table padding) revisit the null block and
    `pl.when` skips their compute."""
    n = cpp
    k_refs = refs[:n]
    v_refs = refs[n:2 * n]
    if quantized:
        ks_refs = refs[2 * n:3 * n]
        vs_refs = refs[3 * n:4 * n]
        o_ref, acc, m_scr, l_scr = refs[4 * n:]
    else:
        o_ref, acc, m_scr, l_scr = refs[2 * n:]
    b = pl.program_id(0)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        _init_scratch(acc, m_scr, l_scr)

    seq_len = lens_ref[b]
    for j in range(cpp):                            # static unroll
        p = c * cpp + j

        @pl.when(p * bs < seq_len)
        def _body(p=p, j=j):
            pos = p * bs + jax.lax.broadcasted_iota(
                jnp.int32, (1, bs), 1)
            valid = pos < seq_len                   # [1, bs]
            if quantized:
                k_blk = _deq(k_refs[j][0], ks_refs[j][0], q_ref.dtype)
                v_blk = _deq(v_refs[j][0], vs_refs[j][0], q_ref.dtype)
            else:
                k_blk = k_refs[j][0]
                v_blk = v_refs[j][0]
            _page_update(q_ref, k_blk, v_blk, acc, m_scr, l_scr,
                         valid, hk=hk, g=g, scale=scale)

    @pl.when(c == nchunks - 1)
    def _finalize():
        _finalize_out(o_ref, acc, l_scr)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "name"))
def paged_decode_attention_kernel(q, k_pool, v_pool, block_tables,
                                  seq_lens, scale=None, interpret=None,
                                  k_scale=None, v_scale=None,
                                  name="paged_decode"):
    """Decode attention over a paged KV cache, fused in one Pallas kernel.

    q [B, Hq, D] (one query token per slot); k_pool/v_pool
    [NB, bs, Hk, D]; block_tables [B, MBPS] int32; seq_lens [B] int32.
    Quantized pools pass int8 k_pool/v_pool plus ``k_scale``/``v_scale``
    [NB, bs, Hk] f32 — the page gather then carries the scale rows and
    dequantizes in VMEM (dequant fusion). Returns [B, Hq, D]. Matches
    `paged_decode_attention_dense` (the dense reference path, same int8
    pool) bitwise-closely; tested one-vs-other. ``name`` is the kernel's
    name in a device trace (``fold_block_rows``' callers pass their own).
    """
    b, hq, d = q.shape
    _, bs, hk, _ = k_pool.shape
    g = hq // hk
    npages = block_tables.shape[1]
    sm_scale = np.float32(scale if scale is not None
                          else 1.0 / math.sqrt(d))
    quantized = k_scale is not None
    if interpret is None:
        interpret = _interpret()

    q_spec = pl.BlockSpec((1, hq, d),
                          lambda bb, pp, tbl, lens: (bb, _I0, _I0))
    pool_spec = pl.BlockSpec((1, bs, hk, d),
                             lambda bb, pp, tbl, lens:
                             (tbl[bb, pp], _I0, _I0, _I0))
    in_specs = [q_spec, pool_spec, pool_spec]
    if quantized:
        scale_spec = pl.BlockSpec((1, bs, hk),
                                  lambda bb, pp, tbl, lens:
                                  (tbl[bb, pp], _I0, _I0))
        in_specs += [scale_spec, scale_spec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, npages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, d),
                               lambda bb, pp, tbl, lens: (bb, _I0, _I0)),
        scratch_shapes=[
            pltpu.VMEM((hq, d), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
        ],
    )
    body = _decode_kernel_q if quantized else _decode_kernel
    kernel = functools.partial(body, hk=hk, g=g, bs=bs,
                               npages=npages, scale=sm_scale)

    scales = (k_scale.astype(jnp.float32),
              v_scale.astype(jnp.float32)) if quantized else ()
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=name + "_q8" if quantized else name,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), q,
      k_pool, v_pool, *scales)


def fold_block_rows(q, hk):
    """A block of L query rows a slot as the kernels' GQA group: q
    [B, L, Hq, D] -> [B, Hk * (L * g), D], a KV head's L x g rows
    contiguous, so ``_page_update`` folds them as it folds the g rows of
    a single query. Every row then sees the same ``seq_lens[b]`` keys
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


# chunk candidates and the per-core VMEM budget the K+V tile may take
# (half of a v5e core's ~16 MiB leaves room for q/out/scratch and the
# double-buffered next chunk)
_CHUNK_CANDIDATES = (2, 4, 8, 16)
_CHUNK_VMEM_BUDGET = 4 * 1024 * 1024


def pick_chunk_pages(npages, bs, hk, d, itemsize=2,
                     budget=_CHUNK_VMEM_BUDGET):
    """Autotune-style static chunk-length pick for the chunked
    flash-decode: the largest candidate (1, 2, 4, 8, 16) whose K+V
    chunk tile (2 pools x cpp x bs x Hk x D x itemsize, doubled for
    pipelining) fits the VMEM ``budget``, never exceeding the table
    length. Pure shape math — deterministic per configuration, so jit
    cache keys stay stable."""
    best = 1
    for cpp in _CHUNK_CANDIDATES:
        if cpp > max(int(npages), 1):
            break
        if 2 * 2 * cpp * bs * hk * d * max(int(itemsize), 1) <= budget:
            best = cpp
    return best


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "chunk_pages", "name"))
def paged_decode_attention_chunked(q, k_pool, v_pool, block_tables,
                                   seq_lens, scale=None, interpret=None,
                                   k_scale=None, v_scale=None,
                                   chunk_pages=None, name="paged_decode"):
    """Chunked flash-decode: :func:`paged_decode_attention_kernel`
    tiling the KV sequence axis ``chunk_pages`` pages per grid step
    (long contexts stop paying one grid step + scratch round-trip per
    page). Same signature/semantics as the per-page kernel, fp32 or
    dequant-fused int8 pools; ``chunk_pages=None`` autotunes via
    :func:`pick_chunk_pages`. The block table pads to a chunk multiple
    with the null block — padding pages sit past every seq_len, so
    `pl.when` skips them."""
    b, hq, d = q.shape
    _, bs, hk, _ = k_pool.shape
    g = hq // hk
    npages = block_tables.shape[1]
    sm_scale = np.float32(scale if scale is not None
                          else 1.0 / math.sqrt(d))
    quantized = k_scale is not None
    if interpret is None:
        interpret = _interpret()
    cpp = int(chunk_pages) if chunk_pages else pick_chunk_pages(
        npages, bs, hk, d, jnp.dtype(q.dtype).itemsize)
    cpp = max(min(cpp, npages), 1)
    if npages % cpp:
        pad = cpp - npages % cpp
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
        npages += pad
    nchunks = npages // cpp

    q_spec = pl.BlockSpec((1, hq, d),
                          lambda bb, cc, tbl, lens: (bb, _I0, _I0))
    in_specs = [q_spec]
    for _ in range(2):          # k pages then v pages
        for j in range(cpp):
            in_specs.append(pl.BlockSpec(
                (1, bs, hk, d),
                lambda bb, cc, tbl, lens, j=j:
                (tbl[bb, cc * cpp + j], _I0, _I0, _I0)))
    if quantized:
        for _ in range(2):      # k scales then v scales
            for j in range(cpp):
                in_specs.append(pl.BlockSpec(
                    (1, bs, hk),
                    lambda bb, cc, tbl, lens, j=j:
                    (tbl[bb, cc * cpp + j], _I0, _I0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nchunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hq, d),
                               lambda bb, cc, tbl, lens: (bb, _I0, _I0)),
        scratch_shapes=[
            pltpu.VMEM((hq, d), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel_chunked, hk=hk, g=g,
                               bs=bs, cpp=cpp, nchunks=nchunks,
                               scale=sm_scale, quantized=quantized)

    # the SAME pool array backs every per-page input; only the BlockSpec
    # index maps differ, so nothing is copied host-side
    ins = [q] + [k_pool] * cpp + [v_pool] * cpp
    if quantized:
        ins += [k_scale.astype(jnp.float32)] * cpp \
            + [v_scale.astype(jnp.float32)] * cpp
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=name + ("_chunked_q8" if quantized else "_chunked"),
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), *ins)
