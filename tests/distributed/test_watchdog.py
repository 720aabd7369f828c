"""Collective watchdog: timeout detection, flight records, heartbeats.

Models the reference's comm watchdog behavior (comm_task_manager.h:37 —
background supervision, timeout detection nccl_comm_task.cc:234, flight
records comm_task_manager.cc:142) at the TPU-native step granularity.
"""

import io
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed.watchdog import (CollectiveWatchdog,
                                             FlightRecorder)

REPO = Path(__file__).resolve().parent.parent.parent


def test_flight_recorder_ring():
    fr = FlightRecorder(capacity=4)
    recs = [fr.start(f"step{i}") for i in range(6)]
    for r in recs:
        fr.finish(r)
    kept = fr.records()
    assert len(kept) == 4
    assert kept[0]["tag"] == "step2"  # oldest two evicted
    assert all(r["status"] == "done" for r in kept)


def test_watchdog_detects_slow_step():
    out = io.StringIO()
    wd = CollectiveWatchdog(timeout=0.3, out=out)
    with wd.watch("wedged_step", {"mesh": "dp4"}):
        time.sleep(0.8)
    assert wd.timed_out.is_set()
    report = out.getvalue()
    assert "wedged_step" in report
    assert "flight records" in report
    assert "python thread stacks" in report
    assert "mesh" in report  # meta propagated


def test_watchdog_quiet_on_fast_step():
    out = io.StringIO()
    wd = CollectiveWatchdog(timeout=5.0, out=out)
    with wd.watch("fast"):
        pass
    assert not wd.timed_out.is_set()
    assert out.getvalue() == ""
    assert wd.recorder.records()[-1]["status"] == "done"


class _DictStore:
    def __init__(self):
        self.kv = {}

    def set(self, k, v):
        self.kv[k] = v

    def get(self, k, timeout=None):
        return self.kv[k]


def test_heartbeat_peer_ages():
    store = _DictStore()
    wd = CollectiveWatchdog(timeout=60, store=store, rank=0, world=2,
                            heartbeat_interval=0.1)
    try:
        time.sleep(0.3)
        ages = wd._hb.peer_ages()
        assert ages[0] is not None and ages[0] < 5.0  # own heartbeat fresh
        assert ages[1] is None                        # peer never appeared
        # stale peer: appeared once, then stopped
        store.set("heartbeat/1", str(time.time() - 120).encode())
        ages = wd._hb.peer_ages()
        assert ages[1] is not None and ages[1] > 100
    finally:
        wd.close()


def test_trainstep_integration_records_steps():
    """FLAGS_enable_collective_watchdog supervises real train steps."""
    from paddle_tpu.distributed import watchdog as wmod

    paddle.set_flags({"FLAGS_enable_collective_watchdog": True})
    wmod._global[0] = CollectiveWatchdog(timeout=300)
    try:
        from paddle_tpu import nn, optimizer

        paddle.seed(0)
        net = nn.Linear(8, 8)
        opt = optimizer.SGD(learning_rate=0.1,
                            parameters=net.parameters())
        step = paddle.jit.TrainStep(
            net, opt, lambda m, x: m(x).square().mean())
        x = paddle.to_tensor(np.ones((4, 8), "float32"))
        step(x)
        step(x)
        recs = wmod._global[0].recorder.records()
        assert len(recs) >= 2
        assert all(r["status"] == "done" for r in recs)
        assert not wmod._global[0].timed_out.is_set()
    finally:
        paddle.set_flags({"FLAGS_enable_collective_watchdog": False})
        wmod._global[0] = None


WEDGED = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from paddle_tpu.distributed.watchdog import CollectiveWatchdog

wd = CollectiveWatchdog(timeout=2.0, fatal=True)

@jax.jit
def wedged(x):
    # an effectively-infinite while loop: the XLA analogue of a hung
    # collective (the program never completes)
    def cond(c):
        return c[0] < jnp.float32(1e30)
    def body(c):
        return (c[0] + jnp.abs(jnp.sin(c[1])).sum() * 1e-9, c[1] * 1.0000001)
    return jax.lax.while_loop(cond, body, (jnp.float32(0), x))

x = jnp.ones((256, 256), jnp.float32)
with wd.watch("wedged_xla_program"):
    out = wedged(x)
    jax.block_until_ready(out)
print("UNREACHABLE")
"""


def test_wedged_program_fatal_timeout(tmp_path):
    """A genuinely hung XLA program is diagnosed and the process aborted
    with the watchdog's exit code."""
    import os
    script = tmp_path / "wedged.py"
    script.write_text(WEDGED)
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(script)], cwd=str(REPO),
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 113, (r.returncode, r.stdout, r.stderr)
    assert "wedged_xla_program" in r.stderr
    assert "flight records" in r.stderr
    assert "UNREACHABLE" not in r.stdout
