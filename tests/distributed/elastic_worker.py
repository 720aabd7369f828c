"""Worker for the elastic scale-in/out E2E test.

Trains a tiny dp-parallel regression; saves a distributed checkpoint
every step and resumes from it on restart, whatever the current world
size (reference fleet/elastic/manager.py fault-tolerance vs
scale-in/out, :456/:483/:506). Scripted life cycle, driven by the
launcher's elastic loop:

- epoch 1 (world 3): rank 2 LEAVES (exit 75) after a few steps
- epoch 2 (world 2): survivors continue from the checkpoint; the test
  posts a join request to the control store
- epoch 3 (world 3): runs to TOTAL_STEPS and exits clean
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=1").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import distributed as dist  # noqa: E402

TOTAL_STEPS = 12
LEAVE_RC = 75


def main():
    out_dir = sys.argv[1]
    epoch = int(os.environ["PADDLE_RESTART_EPOCH"])
    dist.init_parallel_env()
    rank = dist.get_rank()
    world = dist.get_world_size()
    mesh = dist.init_mesh([world], ["dp"])

    # heartbeat into the launcher's control store (lease liveness) from a
    # background thread, so a slow step cannot expire the lease
    # (reference ElasticManager._heartbeat, manager.py:253)
    store_addr = os.environ["PADDLE_ELASTIC_STORE"]
    host, port = store_addr.rsplit(":", 1)
    from paddle_tpu.distributed.store import TCPStore
    control = TCPStore(host, int(port), is_master=False)

    import threading

    def _beat():
        while True:
            control.set(f"hb/{epoch}/{rank}", str(time.time()))
            time.sleep(1.0)

    threading.Thread(target=_beat, daemon=True).start()
    if rank == 0:
        with open(os.path.join(out_dir, "elastic_store"), "w") as f:
            f.write(store_addr)

    # tiny model: w [4] fitting y = 2x (params replicated over dp)
    w = dist.shard_tensor(np.zeros((4,), np.float32), mesh,
                          [dist.Replicate()])
    w.stop_gradient = False
    ckpt = os.path.join(out_dir, "ckpt")
    step0 = 0
    state = {"w": w}
    if os.path.exists(os.path.join(ckpt, "step.json")):
        dist.load_state_dict(state, ckpt)
        with open(os.path.join(ckpt, "step.json")) as f:
            step0 = json.load(f)["step"]

    rng = np.random.default_rng(123)  # same data sequence every life
    xs = rng.standard_normal((TOTAL_STEPS, 6, 4)).astype("float32")
    for step in range(step0, TOTAL_STEPS):
        if world < 3 and step >= 9:
            # the degraded world cannot FINISH the job — park (lease
            # still beating) until the launcher scales back out and
            # restarts us at full world (deterministic scale-out point)
            while True:
                time.sleep(0.5)
        x = paddle.to_tensor(xs[step])
        y = paddle.to_tensor(2.0 * xs[step].sum(axis=1, keepdims=True))
        pred = paddle.matmul(x, w.reshape([4, 1]))
        loss = ((pred - y) ** 2).mean()
        loss.backward()
        w = paddle.to_tensor(w.numpy() - 0.2 * w.grad.numpy(),
                             stop_gradient=False)
        dist.shard_tensor(w, mesh, [dist.Replicate()])
        state = {"w": w}
        lval = float(loss.numpy())
        with open(os.path.join(out_dir, f"trajectory.{epoch}.{rank}"),
                  "a") as f:
            f.write(f"{step} {world} {lval}\n")
        dist.save_state_dict(state, ckpt)
        if rank == 0:
            with open(os.path.join(ckpt, "step.json"), "w") as f:
                json.dump({"step": step + 1}, f)
        time.sleep(0.5)
        if epoch == 1 and rank == 2 and step >= 3:
            # leave WITHOUT the jax.distributed shutdown barrier: a
            # sys.exit would wait for peers at the atexit barrier, time
            # out, and take the whole job down with a fatal
            # coordination-service error masking the leave code
            os._exit(LEAVE_RC)
    print(f"rank {rank} done at world {world}", flush=True)


if __name__ == "__main__":
    main()
