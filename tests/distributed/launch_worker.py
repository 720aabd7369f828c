"""Worker script for the end-to-end launch test (run via
`python -m paddle_tpu.distributed.launch`, one OS process per rank).

Mirrors the reference's communication test scripts
(test/collective/test_communication_api_base.py:64 harness): bootstrap
through init_parallel_env, run a real cross-process collective, then a
multi-host sharded checkpoint save/load round trip.
"""

import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# one CPU device per process -> the 2-process mesh is a real 2-host mesh
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=1").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import distributed as dist  # noqa: E402


def main():
    out_dir = sys.argv[1]
    dist.init_parallel_env()
    rank = dist.get_rank()
    world = dist.get_world_size()
    assert world == 2, f"expected world=2, got {world}"
    assert jax.device_count() == 2, jax.devices()

    # --- cross-process collective: psum over the 2-host mesh -------------
    mesh = dist.init_mesh([2], ["dp"])
    from jax.sharding import NamedSharding, PartitionSpec as P

    local = np.full((1, 4), float(rank + 1), np.float32)
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh.jax_mesh, P("dp")), local, (2, 4))
    total = jax.jit(lambda a: a.sum())(arr)
    # ranks contribute 1s and 2s: sum = 4*1 + 4*2 = 12
    assert float(total) == 12.0, float(total)

    body = jax.jit(jax.shard_map(
        lambda a: jax.lax.psum(a, "dp"), mesh=mesh.jax_mesh,
        in_specs=P("dp"), out_specs=P()))
    reduced = body(arr)
    np.testing.assert_allclose(np.asarray(reduced), np.full((1, 4), 3.0))

    # --- multi-host sharded checkpoint round trip ------------------------
    ckpt_dir = os.path.join(out_dir, "ckpt")
    w = dist.shard_tensor(
        np.arange(8, dtype=np.float32).reshape(2, 4), mesh,
        [dist.Shard(0)])
    dist.checkpoint.save_state_dict({"w": w}, ckpt_dir)

    # load back resharded to replicated and check every element
    target = dist.shard_tensor(np.zeros((2, 4), np.float32), mesh,
                               [dist.Replicate()])
    state = {"w": target}
    dist.checkpoint.load_state_dict(state, ckpt_dir)
    # replicated: this host's local replica carries the full value
    got = np.asarray(state["w"]._data.addressable_shards[0].data)
    np.testing.assert_allclose(got.reshape(-1),
                               np.arange(8, dtype=np.float32))

    with open(os.path.join(out_dir, f"ok.{rank}"), "w") as f:
        f.write("E2E-OK\n")
    print(f"E2E-OK rank={rank}")


if __name__ == "__main__":
    main()
