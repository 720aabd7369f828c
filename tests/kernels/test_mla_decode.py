"""The absorbed latent-attention decode kernel
(``kernels/pallas/mla_decode.py``) over the one row pool, interpreted on
the CPU against its plain ``jax.numpy`` twin and a float64 loop: ragged
lengths, empty slots, a chunk boundary, the route and its counters, and
the copies a page costs, counted in the kernel's jaxpr."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.kernels.pallas import mla_decode as K

B, H, LATENT, ROPE, BS, PAGES = 6, 4, 128, 64, 16, 8
LANES = 256                      # 128 + 64, to whole 128-lane tiles
SCALE = 0.11
KW = dict(latent=LATENT, scale=SCALE)


def _case(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    nb = B * PAGES + 1
    q_row = K.in_lanes(
        jnp.asarray(rng.normal(size=(B, H, LATENT)), dtype),
        jnp.asarray(rng.normal(size=(B, H, ROPE)), dtype), LANES)
    row_pool = K.in_lanes(
        jnp.asarray(rng.normal(size=(nb, BS, 1, LATENT)), dtype),
        jnp.asarray(rng.normal(size=(nb, BS, 1, ROPE)), dtype), LANES)
    tables = jnp.asarray(
        rng.permutation(nb - 1)[:B * PAGES].reshape(B, PAGES) + 1,
        jnp.int32)
    return q_row, row_pool, tables


def _oracle(q_row, row_pool, tables, lens):
    """A loop over slots and heads in float64, the two dot products
    apart."""
    out = np.zeros((B, H, LATENT), np.float64)
    q_row, row_pool = np.asarray(q_row, np.float64), \
        np.asarray(row_pool, np.float64)
    for b in range(B):
        n = int(lens[b])
        if not n:
            continue
        rows = row_pool[np.asarray(tables[b])].reshape(-1, LANES)[:n]
        c, r = rows[:, :LATENT], rows[:, LATENT:LATENT + ROPE]
        s = (q_row[b, :, :LATENT] @ c.T
             + q_row[b, :, LATENT:LATENT + ROPE] @ r.T) * SCALE
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (p / p.sum(-1, keepdims=True)) @ c
    return out


# ragged, an empty slot, one token, exactly a page, a chunk boundary
# (cpp 2: 32 tokens) and one past it, the whole table
LENS = jnp.asarray([0, 1, 16, 32, 33, 128], jnp.int32)


@pytest.mark.parametrize("chunk_pages", [1, 2, 3, 4, None])
def test_the_kernel_is_its_plain_twin(chunk_pages):
    case = _case()
    got = K.mla_decode_attention(*case, LENS, interpret=True,
                                 chunk_pages=chunk_pages, **KW)
    want = K.mla_decode_attention_plain(*case, LENS, **KW)
    assert got.shape == (B, H, LATENT)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(got, _oracle(*case, LENS), atol=2e-6)
    assert not np.asarray(got[0]).any()        # no key: zeros


def test_every_slot_empty_gives_zeros_and_dead_pages_are_never_read():
    q_row, row_pool, tables = _case(seed=1)
    none = jnp.zeros((B,), jnp.int32)
    got = K.mla_decode_attention(q_row, row_pool, tables, none,
                                 interpret=True, **KW)
    assert not np.asarray(got).any()
    # pages past a slot's length hold NaN: a live page's result is clean
    lens = jnp.asarray([5, 0, 17, 0, 40, 3], jnp.int32)
    dead = np.ones((B * PAGES + 1,), bool)
    for b in range(B):
        dead[np.asarray(tables[b, :-(-int(lens[b]) // BS)])] = False
    poisoned = row_pool.at[jnp.asarray(np.flatnonzero(dead))].set(jnp.nan)
    got = K.mla_decode_attention(q_row, poisoned, tables, lens,
                                 interpret=True, chunk_pages=2, **KW)
    want = K.mla_decode_attention_plain(q_row, row_pool, tables, lens,
                                        **KW)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_bfloat16_pools_and_a_narrow_query():
    case = _case(seed=2, dtype=jnp.bfloat16)
    got = K.mla_decode_attention(*case, LENS, interpret=True, **KW)
    want = K.mla_decode_attention_plain(*case, LENS, **KW)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.04)


def test_the_route_and_its_counters():
    from paddle_tpu.profiler import metrics

    case = _case(seed=3)
    before = metrics.snapshot("serving.kernel.mla_decode.")
    plain = K.mla_decode_routed(*case, LENS, kernel_mode="dense", **KW)
    kernel = K.mla_decode_routed(*case, LENS, kernel_mode="pallas", **KW)
    auto = K.mla_decode_routed(*case, LENS, kernel_mode="auto", **KW)
    after = metrics.snapshot("serving.kernel.mla_decode.")
    moved = {k.rsplit(".", 1)[1]: after[k] - before.get(k, 0)
             for k in after}
    assert moved == {"pallas": 1, "plain": 2}   # auto on the CPU is plain
    np.testing.assert_allclose(kernel, plain, atol=2e-6)
    assert (np.asarray(auto) == np.asarray(plain)).all()


def test_the_chunk_pick_fits_the_budget():
    assert K.pick_chunk_pages(256, 16, 640, 32) == 64
    assert K.pick_chunk_pages(8, 16, 640, 32) == 8
    assert K.pick_chunk_pages(256, 16, 640, 32, budget=1 << 20) < 32
    assert K.pick_chunk_pages(0, 16, 640, 32) == 1


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (list, tuple)) else [value]:
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _loop_bodies(jaxpr, found):
    """Every loop body of the kernel, nested ones too."""
    for eqn in jaxpr.eqns:
        for inner in _sub_jaxprs(eqn):
            if eqn.primitive.name in ("while", "scan"):
                found.append(inner)
            _loop_bodies(inner, found)
    return found


def _count(jaxpr, name, deep=False):
    own = sum(e.primitive.name == name for e in jaxpr.eqns)
    return own + (sum(_count(inner, name, True) for e in jaxpr.eqns
                      for inner in _sub_jaxprs(e)) if deep else 0)


def _table_reads(jaxpr):
    """Reads of a block-table entry: ``get`` on the [B, PAGES] int32
    ref."""
    return sum(e.primitive.name == "get"
               and getattr(e.invars[0].aval, "shape", None) == (B, PAGES)
               for e in jaxpr.eqns)


def _kernel_jaxpr(chunk_pages):
    import jax

    top = jax.make_jaxpr(lambda *a: K.mla_decode_attention(
        *a, interpret=False, chunk_pages=chunk_pages, **KW))(*_case(), LENS)
    kernels = []

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.append(eqn.params["jaxpr"])
            for inner in _sub_jaxprs(eqn):
                find(inner)

    find(top.jaxpr)
    assert len(kernels) == 1
    return kernels[0]


def test_a_page_is_one_copy():
    """What the layout is for: a loop that starts copies starts ONE for
    each table entry it reads (the two pools of PR 34 read two). Traced
    at three places (the first chunk of the first live slot, the next
    chunk, the next live slot's first), each a loop of
    ``_START_UNROLL`` pages an iteration and a loop of the rest."""
    starting = [(_count(body, "dma_start"), _table_reads(body))
                for body in _loop_bodies(_kernel_jaxpr(4), [])
                if _count(body, "dma_start")]
    assert sorted(starting) == [(1, 1)] * 3 + [(K._START_UNROLL,) * 2] * 3


@pytest.mark.parametrize("chunk_pages,waits", [(1, 1), (4, 3), (5, 3),
                                               (8, 4)])
def test_a_chunk_is_waited_for_by_its_live_counts_digits(chunk_pages, waits):
    """No loop waits: a chunk's live count (0 to ``chunk_pages``) is
    waited for a binary digit at a time, on a descriptor that many pages
    long."""
    kernel = _kernel_jaxpr(chunk_pages)
    assert _count(kernel, "dma_wait", deep=True) == waits
    assert not any(_count(body, "dma_wait", deep=True)
                   for body in _loop_bodies(kernel, [])
                   if not _count(body, "dot_general", deep=True))
