"""paddle.distributed.rpc over real OS processes.

Reference model: test/rpc/test_rpc_base.py (spawns workers that
init_rpc + call each other through the master endpoint).
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
WORKER = Path(__file__).resolve().parent / "rpc_worker.py"


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_rpc_two_processes(tmp_path):
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get(
            "PYTHONPATH", "")
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINERS_NUM"] = "2"
        env["PADDLE_MASTER"] = f"127.0.0.1:{port}"
        procs.append(subprocess.Popen(
            [sys.executable, str(WORKER), str(tmp_path)],
            env=env, cwd=str(REPO), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    for rank, p in enumerate(procs):
        out, _ = p.communicate(timeout=300)
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-4000:]}"
    for rank in range(2):
        assert (tmp_path / f"rpc_ok.{rank}").exists()


def test_rpc_api_surface():
    from paddle_tpu.distributed import rpc
    for n in ["init_rpc", "rpc_sync", "rpc_async", "shutdown",
              "get_worker_info", "get_all_worker_infos",
              "get_current_worker_info"]:
        assert hasattr(rpc, n)
    try:
        rpc.rpc_sync("nobody", int)
    except RuntimeError as e:
        assert "init_rpc" in str(e)
