"""Absorbed latent-attention (MLA) decode over a paged latent cache
(Pallas).

A layer of latent attention caches ONE row a token, shared by every
head: ``c`` [latent] (the compressed keys *and* values) and ``k_r``
[rope] (the rotary keys). With the up-projection folded into the query
and the output (``models/xing.py``), a head's attention is over the rows
as they lie::

    score_h(s) = scale * (q_lat_h . c(s) + q_rope_h . k_r(s))
    o_lat_h    = softmax_s(score_h) @ c                    [latent]

so the cache is read once for all heads and nothing a head wide is ever
rebuilt. The kernel is ``paged_attention._decode_kernel``'s design (PR
29) at this geometry: a slot a grid step; the block table rides scalar
prefetch and the pools stay in HBM; the slot's *live* pages come
``chunk_pages`` at a time by DMAs the body starts itself, into one of
two buffers, the next chunk's (or the next live slot's first) on its way
while this one is computed; a chunk is ONE tile that all ``H`` query
rows meet in one pair of matmuls, with one online-softmax update. There
is no head mask: every row sees every column below the slot's length.

- ``mla_decode_attention``        the Pallas call, named ``mla_decode``
- ``mla_decode_attention_plain``  the same in ``jax.numpy`` (a gather of
  the slot's pages): the CPU's route, ``dense`` mode, and the oracle
- ``mla_decode_routed``           picks by ``kernel_route`` and counts
  ``serving.kernel.mla_decode.{pallas,plain}`` where it is traced

Decode attention is bound by HBM: a call must read context tokens x
(latent + rope) x item size bytes, for 2 x H x (latent + rope + latent)
FLOPs a context token (at 32 heads of 512 + 64: 60 FLOP a byte, under
the v5e's 240).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...profiler import metrics as _metrics
from .flash_attention import _interpret

__all__ = ["mla_decode_attention", "mla_decode_attention_plain",
           "mla_decode_routed", "pick_chunk_pages"]

# route the absorbed decode attention took, counted where it is traced
_MLA_PALLAS = _metrics.counter("serving.kernel.mla_decode.pallas")
_MLA_PLAIN = _metrics.counter("serving.kernel.mla_decode.plain")

# typed literals: under jax_enable_x64 a bare python number traces as a
# weak 64-bit constant that Mosaic cannot legalize
_NEG = np.float32(-1e30)
_ZERO = np.float32(0.0)
_ONE = np.float32(1.0)
_I0 = np.int32(0)

_CHUNK_CANDIDATES = (1, 2, 4, 8, 16, 32, 64)
_CHUNK_VMEM_BUDGET = 8 * 1024 * 1024


def pick_chunk_pages(npages, bs, latent, rope, rows, itemsize=2,
                     budget=_CHUNK_VMEM_BUDGET):
    """Pages a chunk holds (static): the largest candidate, no longer
    than the table, whose two buffers of dense ``c`` and ``k_r`` pages
    (the rotary part padded to whole 128-lane tiles) and float32 score
    tile (scores, their exponentials, and those in the compute type)
    fit ``budget``."""
    lanes = latent + -(-rope // 128) * 128
    best = 1
    for cpp in _CHUNK_CANDIDATES:
        if cpp > max(int(npages), 1):
            break
        keys = cpp * bs
        if 2 * keys * lanes * itemsize + rows * keys * (8 + itemsize) \
                <= budget:
            best = cpp
    return best


def _mla_kernel(tables_ref, lens_ref, ql_ref, qr_ref, c_hbm, r_hbm, o_ref,
                cbuf, rbuf, sem, parity, acc, m_scr, l_scr, *, bs, cpp,
                scale):
    """A slot a grid step: see the module docstring."""
    moved = ((c_hbm, cbuf), (r_hbm, rbuf))
    b = pl.program_id(0)
    nslots, npages = tables_ref.shape
    cols = cpp * bs
    i32 = np.int32
    bs_i, cpp_i = i32(bs), i32(cpp)
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
        cbuf[...] = jnp.zeros_like(cbuf)
        rbuf[...] = jnp.zeros_like(rbuf)
        parity[0] = _I0
        fetch_first_of_next_live(_I0, _I0)

    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, _NEG)
    l_scr[:] = jnp.zeros_like(l_scr)
    chunk_tokens = i32(cols)
    seq_len = jnp.minimum(lens_ref[b], i32(npages * bs))
    nchunks = (seq_len + chunk_tokens - one) // chunk_tokens
    first_buf = parity[0]
    dtype = ql_ref.dtype
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    contract_last = (((1,), (1,)), ((), ()))

    def chunk_update(i, carry):
        buf = (first_buf + i) % i32(2)

        @pl.when(i + one < nchunks)
        def _():
            chunk_dma(b, i + one, one - buf, True)

        @pl.when(i + one == nchunks)
        def _():
            fetch_first_of_next_live(b + one, one - buf)

        chunk_dma(b, i, buf, False)
        c = cbuf[buf].reshape(cols, cbuf.shape[-1])     # [cols, latent]
        r = rbuf[buf].reshape(cols, rbuf.shape[-1])     # [cols, rope]
        s = (jax.lax.dot_general(ql_ref[0], c, contract_last,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0], r, contract_last,
                                   preferred_element_type=jnp.float32)
             ) * scale                                  # [rows, cols]
        s = jnp.where(i * chunk_tokens + col < seq_len, s, _NEG)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a live chunk holds a live token: m_new is finite, and exp()
        # alone takes the masked columns to 0
        pmat = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pmat, axis=-1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot(
            pmat.astype(dtype), c, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(_I0, nchunks, chunk_update, _I0)
    parity[0] = (first_buf + nchunks) % i32(2)
    l = l_scr[:, :1]                            # 0 where no key: zeros
    o_ref[0] = (acc[:] / jnp.where(l > _ZERO, l, _ONE)).astype(o_ref.dtype)


def _to_lanes(q_rope, lanes):
    """``q_rope`` [.., rope] with zeros behind it, as wide as the pool's
    rows."""
    pad = lanes - q_rope.shape[-1]
    return jnp.pad(q_rope, [(0, 0)] * (q_rope.ndim - 1) + [(0, pad)]) \
        if pad else q_rope


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "chunk_pages", "name"))
def mla_decode_attention(q_lat, q_rope, c_pool, r_pool, block_tables,
                         seq_lens, *, scale, interpret=None,
                         chunk_pages=None, name="mla_decode"):
    """``q_lat`` [B, H, latent] and ``q_rope`` [B, H, rope] (a slot's
    query rows, the up-projection of the keys folded in) over the latent
    pools ``c_pool`` [NB, bs, 1, latent] and ``r_pool`` [NB, bs, 1,
    rope lanes] (the rotary keys in a row of whole 128-lane tiles, which
    is what a DMA moves and how the chip lays a narrower row out anyway:
    zeros behind them) through ``block_tables`` [B, pages] int32, the
    first ``seq_lens`` [B] tokens of each slot. Returns ``o_lat`` [B, H,
    latent] in the queries' dtype: zeros for a slot with no key."""
    b, h, latent = q_lat.shape
    q_rope = _to_lanes(q_rope, r_pool.shape[-1])
    rope = q_rope.shape[-1]
    nb, bs = c_pool.shape[:2]
    if interpret is None:
        interpret = _interpret()
    cpp = int(chunk_pages) if chunk_pages else pick_chunk_pages(
        block_tables.shape[1], bs, latent, rope, h,
        jnp.dtype(c_pool.dtype).itemsize)
    cpp = max(min(cpp, block_tables.shape[1]), 1)

    def per_slot(width):
        return pl.BlockSpec((1, h, width),
                            lambda bb, tbl, lens: (bb, _I0, _I0))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[per_slot(latent), per_slot(rope), in_hbm, in_hbm],
        out_specs=per_slot(latent),
        scratch_shapes=[
            pltpu.VMEM((2, cpp, bs, latent), c_pool.dtype),
            pltpu.VMEM((2, cpp, bs, rope), r_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, latent), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ])
    kernel = functools.partial(_mla_kernel, bs=bs, cpp=cpp,
                               scale=np.float32(scale))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_lat.shape, q_lat.dtype),
        interpret=interpret, name=name,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), q_lat,
      q_rope, c_pool.reshape(nb, bs, latent), r_pool.reshape(nb, bs, rope))


def mla_decode_attention_plain(q_lat, q_rope, c_pool, r_pool, block_tables,
                               seq_lens, *, scale):
    """The plain twin of :func:`mla_decode_attention`: every slot's pages
    gathered ([B, S_max, latent + rope] materialised), positions from
    ``seq_len`` on masked, the softmax in float32."""
    b = q_lat.shape[0]
    latent, rope = c_pool.shape[-1], r_pool.shape[-1]
    q_rope = _to_lanes(q_rope, rope)
    s_max = block_tables.shape[1] * c_pool.shape[1]
    c = c_pool[block_tables].reshape(b, s_max, latent)
    r = r_pool[block_tables].reshape(b, s_max, rope)
    f32 = jnp.float32
    logits = (jnp.einsum("bhc,btc->bht", q_lat, c,
                         preferred_element_type=f32)
              + jnp.einsum("bhr,btr->bht", q_rope, r,
                           preferred_element_type=f32)) * f32(scale)
    mask = (jnp.arange(s_max, dtype=jnp.int32)[None, :]
            < seq_lens[:, None])[:, None, :]
    logits = jnp.where(mask, logits, f32(-1e30))
    # a slot with no key: uniform junk, zeroed
    probs = jnp.where(mask, jax.nn.softmax(logits, axis=-1), 0.0)
    return jnp.einsum("bht,btc->bhc", probs.astype(c.dtype), c,
                      preferred_element_type=f32).astype(q_lat.dtype)


def mla_decode_routed(q_lat, q_rope, c_pool, r_pool, block_tables,
                      seq_lens, *, scale, kernel_mode=None):
    """:func:`mla_decode_attention` where the resolved
    ``FLAGS_paged_kernel`` mode routes to Pallas on this backend
    (``inference.paged.kernel_route``: the TPU, or ``pallas`` forced,
    interpreted on the CPU), the plain twin otherwise."""
    from ...inference.paged import kernel_route
    route = kernel_route(kernel_mode)
    if route == "dense":
        _MLA_PLAIN.inc()
        return mla_decode_attention_plain(
            q_lat, q_rope, c_pool, r_pool, block_tables, seq_lens,
            scale=scale)
    _MLA_PALLAS.inc()
    return mla_decode_attention(
        q_lat, q_rope, c_pool, r_pool, block_tables, seq_lens,
        scale=float(scale), interpret=route == "interpret")
