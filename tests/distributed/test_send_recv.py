"""Eager cross-process send/recv over the native TCPStore channel
(reference python/paddle/distributed/communication/send.py + recv.py,
test discipline of test/collective/: launcher spawns ranks, per-rank
numerics asserted in the worker)."""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist

REPO = Path(__file__).resolve().parent.parent.parent
WORKER = Path(__file__).resolve().parent / "p2p_worker.py"


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_send_recv_two_process_e2e(tmp_path):
    port = _free_port()
    log_dir = tmp_path / "logs"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [
        sys.executable, "-m", "paddle_tpu.distributed.launch",
        "--master", f"127.0.0.1:{port}",
        "--nnodes", "1", "--nproc_per_node", "2",
        "--log_dir", str(log_dir), "--max_restart", "0",
        str(WORKER), str(tmp_path),
    ]
    r = subprocess.run(cmd, env=env, cwd=str(REPO), capture_output=True,
                       text=True, timeout=600)
    logs = "\n".join(f"--- {p.name} ---\n{p.read_text()[-3000:]}"
                     for p in sorted(Path(log_dir).glob("workerlog.*"))) \
        if log_dir.exists() else ""
    assert r.returncode == 0, f"launch rc={r.returncode}\n" \
        f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}\n{logs}"
    assert (tmp_path / "p2p_ok_0").exists(), logs
    assert (tmp_path / "p2p_ok_1").exists(), logs


def test_send_recv_single_process_raises():
    with pytest.raises(RuntimeError, match="multi-process"):
        dist.send(paddle.ones([2]), dst=1)
    with pytest.raises(RuntimeError, match="multi-process"):
        dist.recv(paddle.ones([2]), src=1)
