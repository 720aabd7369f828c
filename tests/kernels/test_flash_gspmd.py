"""The Pallas flash kernel on a device mesh: with the mesh declared
(``kernels.on_mesh``) the call runs under ``jax.shard_map``, batch rows
and KV-head groups per device, and must match the unsharded kernel,
forward and backward — the TPU analogue of the reference's
flash-attention SPMD rule
(`paddle/phi/infermeta/spmd_rules/flash_attention.cc`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.kernels import on_mesh
from paddle_tpu.kernels.pallas.flash_attention import flash_attention


def _mesh(dp, tp):
    devs = jax.devices()
    if len(devs) < dp * tp:
        pytest.skip(f"needs {dp * tp} virtual devices")
    return jax.sharding.Mesh(np.array(devs[:dp * tp]).reshape(dp, tp),
                             ("dp", "tp"))


def _mk(b, s, hq, hk, d, seed=0):
    r = np.random.default_rng(seed)
    return [jnp.asarray(r.standard_normal(shape).astype(np.float32))
            for shape in ((b, s, hq, d), (b, s, hk, d), (b, s, hk, d))]


def _on(mesh):
    return (mesh, ("dp",))


def test_axes_follow_what_divides():
    mesh = _mesh(2, 4)
    assert on_mesh.batch_head_axes(_on(mesh), 4, 8) == (("dp",), ("tp",))
    # 2 kv heads do not split 4 ways, an odd batch not 2 ways: replicate
    assert on_mesh.batch_head_axes(_on(mesh), 4, 2) == (("dp",), None)
    assert on_mesh.batch_head_axes(_on(mesh), 3, 8) == (None, ("dp", "tp"))
    # a serving mesh declares no batch axis: heads over the whole mesh
    assert on_mesh.batch_head_axes((mesh, ()), 1, 8) == (None, ("dp", "tp"))


def test_batch_and_head_sharded_forward_matches():
    mesh = _mesh(2, 4)
    q, k, v = _mk(4, 256, 8, 8, 128)
    ref = np.asarray(flash_attention(q, k, v, causal=True))
    sh = NamedSharding(mesh, P("dp", None, "tp", None))
    out = jax.jit(lambda a, b, c: flash_attention(
        a, b, c, causal=True, on_mesh=_on(mesh)))(
        *(jax.device_put(a, sh) for a in (q, k, v)))
    assert out.sharding.is_equivalent_to(sh, 4)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_sharded_backward_matches():
    mesh = _mesh(2, 4)
    q, k, v = _mk(4, 256, 8, 8, 128, seed=1)

    def loss(ctx):
        return lambda a, b, c: jnp.sum(flash_attention(
            a, b, c, causal=True, on_mesh=ctx).astype(jnp.float32) ** 2)

    g_ref = jax.grad(loss(None), argnums=(0, 1, 2))(q, k, v)
    sh = NamedSharding(mesh, P("dp", None, "tp", None))
    g_sh = jax.jit(jax.grad(loss(_on(mesh)), argnums=(0, 1, 2)))(
        *(jax.device_put(a, sh) for a in (q, k, v)))
    for a, b in zip(g_sh, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_gqa_head_sharded():
    # GQA: 8 query heads in 2 kv groups, one group per tp shard
    mesh = _mesh(2, 2)
    q, k, v = _mk(2, 256, 8, 2, 128, seed=2)
    ref = np.asarray(flash_attention(q, k, v, causal=True))
    sh = NamedSharding(mesh, P("dp", None, "tp", None))
    out = jax.jit(lambda a, b, c: flash_attention(
        a, b, c, causal=True, on_mesh=_on(mesh)))(
        *(jax.device_put(a, sh) for a in (q, k, v)))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_seq_sharded_input_gets_resharded_not_rejected():
    # the specs are constraints: an operand that arrives sequence-sharded
    # is resharded by XLA (correct numerics), not refused
    mesh = _mesh(2, 4)
    q, k, v = _mk(2, 256, 4, 4, 128, seed=3)
    ref = np.asarray(flash_attention(q, k, v, causal=True))
    sh = NamedSharding(mesh, P(None, "dp", None, None))  # seq sharded!
    out = jax.jit(lambda a, b, c: flash_attention(
        a, b, c, causal=True, on_mesh=_on(mesh)))(
        *(jax.device_put(a, sh) for a in (q, k, v)))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_whole_mesh_manual_region_needs_no_wrap():
    """Inside a shard_map over the whole mesh (the pipeline engine, the
    serving mesh's decode attention) the declaration reads as absent and
    the kernel call is local."""
    mesh = _mesh(2, 4)
    seen = []

    def body(x):
        seen.append(on_mesh.current())
        return x

    with on_mesh.kernel_mesh(mesh, ("dp",)):
        assert on_mesh.current() == (mesh, ("dp",))
        jax.shard_map(body, mesh=mesh, in_specs=P("dp", "tp"),
                      out_specs=P("dp", "tp"))(jnp.ones((4, 4)))
        with pytest.raises(NotImplementedError, match="manual over"):
            jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                          out_specs=P("dp"), axis_names={"dp"})(
                jnp.ones((4, 4)))
    assert seen == [None]
    assert on_mesh.current() is None
