"""Ring (context-parallel) attention vs full attention oracle."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import distributed as dist
from paddle_tpu.distributed.ring_attention import ring_attention
from paddle_tpu.kernels.flash_attention import sdpa_xla

@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 64, 4, 16
    return [rng.standard_normal((B, S, H, D)).astype("float32")
            for _ in range(3)]


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_full(qkv, causal):
    q, k, v = qkv
    mesh = dist.init_mesh([8], ["sep"])
    out = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                         paddle.to_tensor(v), mesh=mesh, causal=causal)
    import jax.numpy as jnp
    ref = np.asarray(sdpa_xla(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal))
    np.testing.assert_allclose(out.numpy(), ref, atol=2e-5, rtol=1e-4)


def test_ring_backward(qkv):
    q, k, v = qkv
    mesh = dist.init_mesh([4], ["sep"])
    qt = paddle.to_tensor(q)
    qt.stop_gradient = False
    kt = paddle.to_tensor(k)
    kt.stop_gradient = False
    vt = paddle.to_tensor(v)
    vt.stop_gradient = False
    out = ring_attention(qt, kt, vt, mesh=mesh, causal=True)
    out.sum().backward()

    # oracle grads from the dense path
    import jax
    import jax.numpy as jnp

    def ref_loss(qa, ka, va):
        return jnp.sum(sdpa_xla(qa, ka, va, causal=True))

    gq, gk, gv = jax.grad(ref_loss, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(qt.grad.numpy(), np.asarray(gq), atol=2e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(kt.grad.numpy(), np.asarray(gk), atol=2e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(vt.grad.numpy(), np.asarray(gv), atol=2e-4,
                               rtol=1e-3)


def test_ring_gqa(qkv):
    q, k, v = qkv
    mesh = dist.init_mesh([4], ["sep"])
    k2, v2 = k[:, :, :2], v[:, :, :2]  # 2 kv heads vs 4 q heads
    out = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k2),
                         paddle.to_tensor(v2), mesh=mesh, causal=True)
    import jax.numpy as jnp
    ref = np.asarray(sdpa_xla(
        jnp.asarray(q), jnp.repeat(jnp.asarray(k2), 2, 2),
        jnp.repeat(jnp.asarray(v2), 2, 2), causal=True))
    np.testing.assert_allclose(out.numpy(), ref, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_kernel_path(qkv, causal):
    """Pallas-kernel ring body (per-chunk flash + logsumexp merge) matches
    the dense oracle (interpret mode on the CPU mesh)."""
    q, k, v = qkv
    mesh = dist.init_mesh([2], ["sep"])
    out = ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                         paddle.to_tensor(v), mesh=mesh, causal=causal,
                         use_flash=True)
    import jax.numpy as jnp
    ref = np.asarray(sdpa_xla(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal))
    np.testing.assert_allclose(out.numpy(), ref, atol=5e-5, rtol=5e-4)


def test_ring_flash_gqa_and_backward(qkv):
    q, k, v = qkv
    mesh = dist.init_mesh([2], ["sep"])
    k2, v2 = k[:, :, :2], v[:, :, :2]
    qt = paddle.to_tensor(q)
    qt.stop_gradient = False
    out = ring_attention(qt, paddle.to_tensor(k2), paddle.to_tensor(v2),
                         mesh=mesh, causal=True, use_flash=True)
    import jax
    import jax.numpy as jnp
    ref = np.asarray(sdpa_xla(
        jnp.asarray(q), jnp.repeat(jnp.asarray(k2), 2, 2),
        jnp.repeat(jnp.asarray(v2), 2, 2), causal=True))
    np.testing.assert_allclose(out.numpy(), ref, atol=5e-5, rtol=5e-4)
    out.sum().backward()

    def ref_loss(qa):
        return jnp.sum(sdpa_xla(qa, jnp.repeat(jnp.asarray(k2), 2, 2),
                                jnp.repeat(jnp.asarray(v2), 2, 2),
                                causal=True))

    gq = jax.grad(ref_loss)(jnp.asarray(q))
    np.testing.assert_allclose(qt.grad.numpy(), np.asarray(gq),
                               atol=5e-4, rtol=2e-3)
