"""The selective-scan kernels (kernels/pallas/selective_scan.py),
interpreted, against their plain ``jax.numpy`` routes: the prefill's
chunked scan at odd true lengths, at chunk edges and across E-tiles, and
the decode step's in-place state update with idle slots between live
ones. The Jamba geometry of the paged decode kernel (20 query rows a
slot on one KV head: not a multiple of the 8-row tile) is held to the
dense route here too.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401 — sets the process's jax flags
from paddle_tpu.kernels.pallas import selective_scan as ss

N = 16


def _a_t(e):
    """A as the model initialises it, transposed: A[n, e] = -(n + 1)."""
    return -jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, e))


def _scan_case(s, e, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (draw(s, e) - 3.0, draw(s, e).astype(dtype), draw(s, N),
            draw(s, N), _a_t(e), draw(e), draw(N, e))


# (positions, channels, true length): one chunk and several, a length
# that ends inside the first group of 8, on a chunk's edge, one past it,
# the whole bucket; E of one tile, of several, of no tile's multiple
@pytest.mark.parametrize("s, e, true_len", [
    (16, 64, 1), (16, 64, 11), (24, 64, 24), (128, 128, 128),
    (256, 640, 127), (256, 640, 128), (256, 640, 129), (384, 1024, 300),
    (8, 192, 5)])
def test_scan_kernel_matches_the_plain_scan(s, e, true_len):
    args = (*_scan_case(s, e, seed=s + true_len), jnp.int32(true_len))
    y0, h0 = ss.selective_scan_plain(*args)
    y1, h1 = ss.selective_scan(*args, interpret=True)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(y1)[:true_len],
                               np.asarray(y0)[:true_len],
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("true_len", [3, 40, 64])
def test_padding_leaves_the_state_of_the_true_length(true_len):
    """Positions at or past ``true_len`` do not advance ``h``: a padded
    bucket ends where the unpadded sequence does, whatever the padding
    holds."""
    dt, c, b, cm, a_t, d, h0 = _scan_case(64, 128, seed=7)
    n = jnp.int32(true_len)
    keep = -(-true_len // 8) * 8
    _, short = ss.selective_scan(dt[:keep], c[:keep], b[:keep], cm[:keep],
                                 a_t, d, h0, n, interpret=True)
    junk = jnp.where(jnp.arange(64)[:, None] >= true_len, 50.0, 0.0)
    _, padded = ss.selective_scan(dt + junk, c + junk, b, cm, a_t, d, h0,
                                  n, interpret=True)
    np.testing.assert_array_equal(np.asarray(padded), np.asarray(short))


def test_scan_kernel_bf16_activations():
    args = (*_scan_case(64, 256, seed=3, dtype=jnp.bfloat16),
            jnp.int32(50))
    y0, h0 = ss.selective_scan_plain(*args)
    y1, h1 = ss.selective_scan(*args, interpret=True)
    assert y1.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(y1[:50], np.float32),
                               np.asarray(y0[:50]), atol=5e-2, rtol=2e-2)


def test_scan_refuses_a_length_that_is_no_multiple_of_eight():
    args = (*_scan_case(12, 64, seed=0), jnp.int32(5))
    with pytest.raises(ValueError, match="multiple of 8"):
        ss.selective_scan(*args, interpret=True)


def _update_case(layers, slots, e, seed):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (draw(layers, slots, N, e),
            (jax.nn.softplus(draw(slots, e) - 3.0), draw(slots, e),
             draw(slots, N), draw(slots, N), _a_t(e), draw(e)))


@pytest.mark.parametrize("slots, e, layer, active", [
    (8, 128, 0, [1, 1, 1, 1, 1, 1, 1, 1]),
    (12, 256, 2, [0, 1, 0, 0, 1, 1, 0, 1, 0, 0, 0, 1]),   # 12: one tile
    (16, 1280, 1, [1] * 7 + [0] * 9),                     # two slot tiles
    (3, 64, 1, [0, 0, 0])])
def test_update_kernel_matches_the_plain_update(slots, e, layer, active):
    states, args = _update_case(3, slots, e, seed=slots + layer)
    active = jnp.asarray(active, bool)
    s0, y0 = ss.state_update_plain(states, layer, *args, active)
    s1, y1 = ss.state_update(states, layer, *args, active, interpret=True)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               atol=2e-4, rtol=2e-4)
    # the other layers and the idle slots are bit for bit what they were
    idle = ~np.asarray(active)
    for other in {0, 1, 2} - {layer}:
        np.testing.assert_array_equal(np.asarray(s1[other]),
                                      np.asarray(states[other]))
    np.testing.assert_array_equal(np.asarray(s1[layer])[idle],
                                  np.asarray(states[layer])[idle])


def test_a_decode_step_is_the_scan_of_one_position():
    """The two kernels compute one recurrence: a scan over S positions
    is S state updates of one slot."""
    dt, c, b, cm, a_t, d, h0 = _scan_case(16, 128, seed=11)
    _, want = ss.selective_scan(dt, c, b, cm, a_t, d, h0, jnp.int32(16),
                                interpret=True)
    states = h0[None, None]
    for t in range(16):
        states, _ = ss.state_update(
            states, 0, jax.nn.softplus(dt[t:t + 1]), c[t:t + 1],
            b[t:t + 1], cm[t:t + 1], a_t, d, jnp.ones((1,), bool),
            interpret=True)
    np.testing.assert_allclose(np.asarray(states[0, 0]), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mode, scan, update", [
    ("pallas", "pallas", "pallas"), ("dense", "plain", "plain"),
    ("auto", "plain", "plain")])   # auto on the CPU is the plain route
def test_the_route_is_attentions_and_is_counted(mode, scan, update):
    from paddle_tpu.profiler import metrics

    before = metrics.snapshot("serving.kernel.ssm_")
    args = (*_scan_case(8, 64, seed=1), jnp.int32(8))
    ss.selective_scan_routed(*args, kernel_mode=mode)
    states, uargs = _update_case(1, 2, 64, seed=1)
    ss.state_update_routed(states, 0, *uargs, jnp.ones((2,), bool),
                           kernel_mode=mode)
    after = metrics.snapshot("serving.kernel.ssm_")
    moved = {k.rsplit("kernel.", 1)[1] for k in after
             if after[k] != before.get(k, 0)}
    assert moved == {f"ssm_scan.{scan}", f"ssm_update.{update}"}


@pytest.mark.parametrize("kernel", ["page", "chunked"])
@pytest.mark.parametrize("lens", [[1, 100, 37], [0, 128, 16]])
def test_paged_kernel_at_twenty_rows_on_one_kv_head(kernel, lens):
    """Jamba's attention geometry: 20 query heads share one KV head of
    128, so a slot's 20 query rows are no multiple of the 8-row tile."""
    from paddle_tpu.inference.paged import paged_decode_attention_dense
    from tests.kernels.test_paged_attention import _KERNELS, _case

    q, kp, vp, tbl, sl = _case(3, 20, 1, 128, 16, 8, lens)
    dense = paged_decode_attention_dense(q, kp, vp, tbl, sl)
    kern = _KERNELS[kernel](q, kp, vp, tbl, sl)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               atol=5e-5, rtol=1e-4)
