"""paddle.distributed.communication(.stream) module-path parity and
behavior of the stream collective variants (reference:
python/paddle/distributed/communication/stream/).
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import distributed as dist


def test_module_paths():
    assert dist.stream is dist.communication.stream
    for n in ["all_gather", "all_reduce", "alltoall", "alltoall_single",
              "broadcast", "reduce", "reduce_scatter", "recv", "scatter",
              "send", "gather"]:
        assert hasattr(dist.stream, n), n
    assert hasattr(dist.communication, "ReduceOp")
    assert hasattr(dist.communication.group, "is_initialized")
    assert dist.communication.group.destroy_process_group() is None


def test_stream_all_reduce_inside_shard_map():
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("x",))

    def body(a):
        t = paddle.to_tensor(a)
        task = dist.stream.all_reduce(t, group=dist.new_group(
            axis_name="x"))
        task.wait()
        assert task.is_completed()
        return t._data

    x = np.arange(4, dtype=np.float32).reshape(4, 1)
    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                                out_specs=P("x")))(x)
    np.testing.assert_allclose(np.asarray(out).reshape(-1),
                               np.full(4, x.sum()))


def test_autotune_set_config():
    from paddle_tpu.incubate import autotune
    from paddle_tpu.kernels.pallas import flash_attention as fa

    try:
        autotune.set_config({"kernel": {"enable": True}})
        assert fa._AUTOTUNE["enable"]
        assert autotune.get_config()["kernel"]["enable"]
        autotune.set_config({"kernel": {"enable": False}})
        assert not fa._AUTOTUNE["enable"]
        with pytest.raises(ValueError, match="unknown autotune domain"):
            autotune.set_config({"nope": True})
        # json file form
        import json
        import tempfile
        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as f:
            json.dump({"kernel": {"enable": True},
                       "dataloader": {"enable": True}}, f)
        autotune.set_config(f.name)
        assert fa._AUTOTUNE["enable"]
    finally:
        autotune.set_config({"kernel": {"enable": False}})


def test_flash_attention_with_autotune_on_cpu_falls_back():
    """On CPU (interpret mode) the sweep is skipped; results stay exact."""
    import jax.numpy as jnp
    from paddle_tpu.incubate import autotune
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    try:
        autotune.set_config({"kernel": {"enable": True}})
        out = flash_attention(q, k, v, causal=True)
    finally:
        autotune.set_config({"kernel": {"enable": False}})
    # dense oracle
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(32)
    mask = np.tril(np.ones((128, 128), bool))
    s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3)
