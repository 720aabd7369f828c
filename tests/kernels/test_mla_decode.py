"""The absorbed latent-attention decode kernel
(``kernels/pallas/mla_decode.py``) interpreted on the CPU against its
plain ``jax.numpy`` twin: ragged lengths, empty slots, a chunk boundary,
the route and its counters."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.kernels.pallas import mla_decode as K

B, H, LATENT, ROPE, BS, PAGES = 6, 4, 128, 64, 16, 8
SCALE = 0.11


def _case(seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    nb = B * PAGES + 1
    q_lat = jnp.asarray(rng.normal(size=(B, H, LATENT)), dtype)
    q_rope = jnp.asarray(rng.normal(size=(B, H, ROPE)), dtype)
    c_pool = jnp.asarray(rng.normal(size=(nb, BS, 1, LATENT)), dtype)
    r_pool = jnp.zeros((nb, BS, 1, 128), dtype).at[..., :ROPE].set(
        jnp.asarray(rng.normal(size=(nb, BS, 1, ROPE)), dtype))
    tables = jnp.asarray(
        rng.permutation(nb - 1)[:B * PAGES].reshape(B, PAGES) + 1,
        jnp.int32)
    return q_lat, q_rope, c_pool, r_pool, tables


def _oracle(q_lat, q_rope, c_pool, r_pool, tables, lens):
    """A loop over slots and heads in float64."""
    out = np.zeros(q_lat.shape, np.float64)
    c_pool, r_pool = np.asarray(c_pool, np.float64), \
        np.asarray(r_pool, np.float64)
    for b in range(q_lat.shape[0]):
        n = int(lens[b])
        if not n:
            continue
        c = c_pool[np.asarray(tables[b])].reshape(-1, LATENT)[:n]
        r = r_pool[np.asarray(tables[b])].reshape(-1, 128)[:n, :ROPE]
        s = (np.asarray(q_lat[b], np.float64) @ c.T
             + np.asarray(q_rope[b], np.float64) @ r.T) * SCALE
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (p / p.sum(-1, keepdims=True)) @ c
    return out


# ragged, an empty slot, one token, exactly a page, a chunk boundary
# (cpp 2: 32 tokens) and one past it, the whole table
LENS = jnp.asarray([0, 1, 16, 32, 33, 128], jnp.int32)


@pytest.mark.parametrize("chunk_pages", [1, 2, 4, None])
def test_the_kernel_is_its_plain_twin(chunk_pages):
    case = _case()
    got = K.mla_decode_attention(*case, LENS, scale=SCALE, interpret=True,
                                 chunk_pages=chunk_pages)
    want = K.mla_decode_attention_plain(*case, LENS, scale=SCALE)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(got, _oracle(*case, LENS), atol=2e-6)
    assert not np.asarray(got[0]).any()        # no key: zeros


def test_every_slot_empty_gives_zeros_and_dead_pages_are_never_read():
    q_lat, q_rope, c_pool, r_pool, tables = _case(seed=1)
    none = jnp.zeros((B,), jnp.int32)
    got = K.mla_decode_attention(q_lat, q_rope, c_pool, r_pool, tables,
                                 none, scale=SCALE, interpret=True)
    assert not np.asarray(got).any()
    # pages past a slot's length hold NaN: a live page's result is clean
    lens = jnp.asarray([5, 0, 17, 0, 40, 3], jnp.int32)
    dead = np.ones((B * PAGES + 1,), bool)
    for b in range(B):
        dead[np.asarray(tables[b, :-(-int(lens[b]) // BS)])] = False
    poisoned = c_pool.at[jnp.asarray(np.flatnonzero(dead))].set(jnp.nan)
    got = K.mla_decode_attention(q_lat, q_rope, poisoned, r_pool, tables,
                                 lens, scale=SCALE, interpret=True,
                                 chunk_pages=2)
    want = K.mla_decode_attention_plain(q_lat, q_rope, c_pool, r_pool,
                                        tables, lens, scale=SCALE)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_bfloat16_pools_and_a_narrow_query():
    case = _case(seed=2, dtype=jnp.bfloat16)
    got = K.mla_decode_attention(*case, LENS, scale=SCALE, interpret=True)
    want = K.mla_decode_attention_plain(*case, LENS, scale=SCALE)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.04)


def test_the_route_and_its_counters():
    from paddle_tpu.profiler import metrics

    case = _case(seed=3)
    before = metrics.snapshot("serving.kernel.mla_decode.")
    plain = K.mla_decode_routed(*case, LENS, scale=SCALE,
                                kernel_mode="dense")
    kernel = K.mla_decode_routed(*case, LENS, scale=SCALE,
                                 kernel_mode="pallas")
    auto = K.mla_decode_routed(*case, LENS, scale=SCALE, kernel_mode="auto")
    after = metrics.snapshot("serving.kernel.mla_decode.")
    moved = {k.rsplit(".", 1)[1]: after[k] - before.get(k, 0)
             for k in after}
    assert moved == {"pallas": 1, "plain": 2}   # auto on the CPU is plain
    np.testing.assert_allclose(kernel, plain, atol=2e-6)
    assert (np.asarray(auto) == np.asarray(plain)).all()


def test_the_chunk_pick_fits_the_budget():
    assert K.pick_chunk_pages(256, 16, 512, 64, 32) == 64
    assert K.pick_chunk_pages(8, 16, 512, 64, 32) == 8
    assert K.pick_chunk_pages(256, 16, 512, 64, 32, budget=1 << 20) < 32
    assert K.pick_chunk_pages(0, 16, 512, 64, 32) == 1
