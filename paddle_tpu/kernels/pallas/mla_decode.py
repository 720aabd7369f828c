"""Absorbed latent-attention (MLA) decode over a paged latent cache
(Pallas).

A layer of latent attention caches ONE row a token, shared by every
head: ``c`` [latent] (the compressed keys *and* values) and ``k_r``
[rope] (the rotary keys), in ONE pool whose rows are ``lanes`` wide
(``inference.paged.LatentRowSpec``: whole 128-lane tiles, zeros behind
the rotary keys). With the up-projection folded into the query and the
output (``models/xing.py``), a head's attention is over the rows as they
lie::

    score_h(s) = scale * (q_lat_h . c(s) + q_rope_h . k_r(s))
               = scale * (q_row_h . row(s))      q_row = (q_lat, q_rope, 0)
    o_lat_h    = softmax_s(score_h) @ c                    [latent]

so the cache is read once for all heads and nothing a head wide is ever
rebuilt. The kernel is ``paged_attention._decode_kernel``'s design (PR
29) at this geometry: a slot a grid step; the block table rides scalar
prefetch and the pool stays in HBM; the slot's *live* pages come
``chunk_pages`` at a time by DMAs the body starts itself, ONE a page,
into one of two buffers, the next chunk's (or the next live slot's
first) on its way while this one is computed; a chunk is ONE tile that
all ``H`` query rows meet in one matmul over all lanes for the scores
and one for ``p @ rows`` (the wrapper keeps ``[..., :latent]``), with
one online-softmax update. There is no head mask: every row sees every
column below the slot's length.

Why one pool, one wait a binary digit and eight starts a loop
iteration: the kernel pays for a page in scalar work (a table entry, two
addresses, a descriptor, a loop iteration), whatever the copy's size and
in the one instruction stream the matmuls are in, so it adds to them and
only the transfer hides. Measured on a v5e at the served shapes (128
slots of 32 heads over ~1.2 k rows each; the kernel alone, PERF.md 6, PR
35): 0.77 ms a call with the row in two pools (two copies a page), 0.66
in one, 0.61 waiting for a chunk by the digits of its live count and not
a page at a time, 0.50-0.52 starting eight pages a loop iteration. In
``xing29b-reasoning-saturated`` a call is 0.48 ms at 54 % of what its
useful bytes allow (0.78 ms at 33.5 % before). What is left is in
PERF.md 7.14.

- ``mla_decode_attention``        the Pallas call, named ``mla_decode``
- ``mla_decode_attention_plain``  the same in ``jax.numpy`` (a gather of
  the slot's pages): the CPU's route, ``dense`` mode, and the oracle
- ``mla_decode_routed``           picks by ``kernel_route`` and counts
  ``serving.kernel.mla_decode.{pallas,plain}`` where it is traced
- ``in_lanes``                    a row, or a query, laid out in lanes

Decode attention is bound by HBM: a call must read context tokens x
(latent + rope) x item size bytes (``lanes`` x item size as laid out),
for 2 x H x (latent + rope + latent) FLOPs a context token (at 32 heads
of 512 + 64: 60 FLOP a byte, under the v5e's 240).
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
           "mla_decode_routed", "pick_chunk_pages", "in_lanes"]

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
# copies started a loop iteration (measured on the v5e at the served
# shapes: 1 -> 4 took 0.085 ms off a 0.61 ms call, 4 -> 8 nothing more)
_START_UNROLL = 8


def pick_chunk_pages(npages, bs, lanes, rows, itemsize=2,
                     budget=_CHUNK_VMEM_BUDGET):
    """Pages a chunk holds (static): the largest candidate, no longer
    than the table, whose two buffers of dense row pages (``lanes``
    wide) and float32 score tile (scores, their exponentials, and those
    in the compute type) fit ``budget``."""
    best = 1
    for cpp in _CHUNK_CANDIDATES:
        if cpp > max(int(npages), 1):
            break
        keys = cpp * bs
        if 2 * keys * lanes * itemsize + rows * keys * (8 + itemsize) \
                <= budget:
            best = cpp
    return best


def _mla_kernel(tables_ref, lens_ref, q_ref, rows_hbm, o_ref, buf, sem,
                parity, acc, m_scr, l_scr, *, bs, cpp, scale):
    """A slot a grid step: see the module docstring."""
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

    def chunk_start(slot, chunk, which):
        """Start the copies of a chunk's live pages: ONE a page, a
        token's whole row. ``_START_UNROLL`` pages a loop iteration, so
        that the scalar work of one start (the table entry, two
        addresses) overlaps the next's; the rest page by page."""
        def page(j):
            block = tables_ref[slot, chunk * cpp_i + j]
            pltpu.make_async_copy(
                rows_hbm.at[block], buf.at[which, j], sem.at[which]).start()

        def group(g, carry):
            for k in range(_START_UNROLL):
                page(g * i32(_START_UNROLL) + i32(k))
            return carry

        def single(j, carry):
            page(j)
            return carry

        n = live_pages(slot, chunk)
        groups = n // i32(_START_UNROLL)
        jax.lax.fori_loop(_I0, groups, group, _I0)
        jax.lax.fori_loop(groups * i32(_START_UNROLL), n, single, _I0)

    def chunk_wait(slot, chunk, which):
        """Wait for a chunk's copies. A DMA semaphore counts bytes, and a
        wait takes from it what its descriptor would have moved: the
        live count's binary digits, a wait each on a descriptor that many
        pages long (at most seven), where a wait a page re-read the table
        and rebuilt a descriptor only to learn a page's size."""
        n = live_pages(slot, chunk)
        pages = 1 << (cpp.bit_length() - 1)
        while pages:
            @pl.when((n & i32(pages)) != _I0)
            def _(pages=pages):
                pltpu.make_async_copy(
                    rows_hbm.at[pl.ds(0, pages)],
                    buf.at[which, pl.ds(0, pages)], sem.at[which]).wait()
            pages //= 2

    def fetch_first_of_next_live(after, which):
        nxt = jax.lax.while_loop(
            lambda s: jnp.logical_and(
                s < i32(nslots),
                lens_ref[jnp.minimum(s, last_slot)] <= _I0),
            lambda s: s + one, after)

        @pl.when(nxt < i32(nslots))
        def _():
            chunk_start(nxt, _I0, which)

    @pl.when(b == 0)
    def _prologue():
        # what a buffer holds past a chunk's live pages is masked, not
        # multiplied away: it has to be finite from the start
        buf[...] = jnp.zeros_like(buf)
        parity[0] = _I0
        fetch_first_of_next_live(_I0, _I0)

    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, _NEG)
    l_scr[:] = jnp.zeros_like(l_scr)
    chunk_tokens = i32(cols)
    seq_len = jnp.minimum(lens_ref[b], i32(npages * bs))
    nchunks = (seq_len + chunk_tokens - one) // chunk_tokens
    first_buf = parity[0]
    dtype = q_ref.dtype
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    contract_last = (((1,), (1,)), ((), ()))

    def chunk_update(i, carry):
        which = (first_buf + i) % i32(2)

        @pl.when(i + one < nchunks)
        def _():
            chunk_start(b, i + one, one - which)

        @pl.when(i + one == nchunks)
        def _():
            fetch_first_of_next_live(b + one, one - which)

        chunk_wait(b, i, which)
        rows = buf[which].reshape(cols, buf.shape[-1])  # [cols, lanes]
        # the query's lanes behind its rotary part are zeros, as the
        # rows' are: one matmul over all lanes is both dot products
        s = jax.lax.dot_general(
            q_ref[0], rows, contract_last,
            preferred_element_type=jnp.float32) * scale  # [heads, cols]
        s = jnp.where(i * chunk_tokens + col < seq_len, s, _NEG)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a live chunk holds a live token: m_new is finite, and exp()
        # alone takes the masked columns to 0
        pmat = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pmat, axis=-1, keepdims=True)
        # over all lanes too: the wrapper keeps the compressed part
        acc[:] = acc[:] * alpha + jax.lax.dot(
            pmat.astype(dtype), rows, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(_I0, nchunks, chunk_update, _I0)
    parity[0] = (first_buf + nchunks) % i32(2)
    l = l_scr[:, :1]                            # 0 where no key: zeros
    o_ref[0] = (acc[:] / jnp.where(l > _ZERO, l, _ONE)).astype(o_ref.dtype)


def in_lanes(first, second, lanes):
    """A row as the pool lays it out: ``first`` [.., latent] (``c``, or
    a query's ``q_lat``), ``second`` [.., rope] behind it (the rotary
    keys, or ``q_rope``), zeros to ``lanes``."""
    pad = lanes - first.shape[-1] - second.shape[-1]
    return jnp.concatenate(
        [first, second, jnp.zeros(first.shape[:-1] + (pad,), first.dtype)],
        axis=-1)


@functools.partial(jax.jit, static_argnames=("latent", "scale", "interpret",
                                             "chunk_pages", "name"))
def mla_decode_attention(q_row, row_pool, block_tables, seq_lens, *, latent,
                         scale, interpret=None, chunk_pages=None,
                         name="mla_decode"):
    """``q_row`` [B, H, lanes] (a slot's query rows as :func:`in_lanes`
    lays them, the up-projection of the keys folded in) over the latent
    pool ``row_pool`` [NB, bs, 1, lanes] (a token's row: ``latent``
    compressed values, the rotary keys, zeros to whole 128-lane tiles,
    which is what a DMA moves and how the chip lays a narrower row out
    anyway) through ``block_tables`` [B, pages] int32, the first
    ``seq_lens`` [B] tokens of each slot. Returns ``o_lat`` [B, H,
    latent] in the queries' dtype: zeros for a slot with no key."""
    b, h, lanes = q_row.shape
    nb, bs = row_pool.shape[:2]
    if interpret is None:
        interpret = _interpret()
    cpp = int(chunk_pages) if chunk_pages else pick_chunk_pages(
        block_tables.shape[1], bs, lanes, h,
        jnp.dtype(row_pool.dtype).itemsize)
    # a wait's descriptor spans up to a chunk of the pool's blocks
    cpp = max(min(cpp, block_tables.shape[1], nb), 1)
    per_slot = pl.BlockSpec((1, h, lanes),
                            lambda bb, tbl, lens: (bb, _I0, _I0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[per_slot, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=per_slot,
        scratch_shapes=[
            pltpu.VMEM((2, cpp, bs, lanes), row_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, lanes), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
        ])
    kernel = functools.partial(_mla_kernel, bs=bs, cpp=cpp,
                               scale=np.float32(scale))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_row.shape, q_row.dtype),
        interpret=interpret, name=name,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32), q_row,
      row_pool.reshape(nb, bs, lanes))[..., :latent]


def mla_decode_attention_plain(q_row, row_pool, block_tables, seq_lens, *,
                               latent, scale):
    """The plain twin of :func:`mla_decode_attention`: every slot's pages
    gathered ([B, S_max, lanes] materialised), positions from
    ``seq_len`` on masked, the softmax in float32."""
    b, _, lanes = q_row.shape
    s_max = block_tables.shape[1] * row_pool.shape[1]
    rows = row_pool[block_tables].reshape(b, s_max, lanes)
    f32 = jnp.float32
    logits = jnp.einsum("bhl,btl->bht", q_row, rows,
                        preferred_element_type=f32) * f32(scale)
    mask = (jnp.arange(s_max, dtype=jnp.int32)[None, :]
            < seq_lens[:, None])[:, None, :]
    logits = jnp.where(mask, logits, f32(-1e30))
    # a slot with no key: uniform junk, zeroed
    probs = jnp.where(mask, jax.nn.softmax(logits, axis=-1), 0.0)
    c = rows[..., :latent]
    return jnp.einsum("bht,btc->bhc", probs.astype(c.dtype), c,
                      preferred_element_type=f32).astype(q_row.dtype)


def mla_decode_routed(q_row, row_pool, block_tables, seq_lens, *, latent,
                      scale, kernel_mode=None):
    """:func:`mla_decode_attention` where the resolved
    ``FLAGS_paged_kernel`` mode routes to Pallas on this backend
    (``inference.paged.kernel_route``: the TPU, or ``pallas`` forced,
    interpreted on the CPU), the plain twin otherwise."""
    from ...inference.paged import kernel_route
    route = kernel_route(kernel_mode)
    if route == "dense":
        _MLA_PLAIN.inc()
        return mla_decode_attention_plain(
            q_row, row_pool, block_tables, seq_lens, latent=latent,
            scale=scale)
    _MLA_PALLAS.inc()
    return mla_decode_attention(
        q_row, row_pool, block_tables, seq_lens, latent=latent,
        scale=float(scale), interpret=route == "interpret")
