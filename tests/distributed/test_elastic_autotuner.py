"""Elastic membership/restart + AutoTuner search logic.

Reference model: test/collective/fleet/test_elastic_manager.py (watch
transitions, lease expiry) and test/auto_tuner/ (prune + search).
Includes a real elastic-restart E2E: a worker that crashes on its first
life and is relaunched by the launch CLI.
"""

import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

from paddle_tpu.distributed.auto_tuner import AutoTuner, default_candidates
from paddle_tpu.distributed.elastic import ElasticManager, ElasticStatus

REPO = Path(__file__).resolve().parent.parent.parent


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_elastic_watch_transitions():
    master = ElasticManager(port=0, np=2, node_id=0, is_master=True,
                            heartbeat_interval=0.1, lease_ttl=0.8)
    peer = ElasticManager(port=master.port, np=2, node_id=1,
                          heartbeat_interval=0.1, lease_ttl=0.8)
    master.register()
    peer.register()
    time.sleep(0.3)
    assert master.alive_nodes() == [0, 1]
    assert master.watch() == ElasticStatus.HOLD

    # scale-in: peer dies (heartbeat stops, lease expires)
    peer.exit(completed=False)
    time.sleep(1.0)
    assert master.alive_nodes() == [0]
    assert master.watch() == ElasticStatus.RESTART

    # restart epoch propagates through the store
    e0 = master.restart_epoch()
    master.signal_restart()
    assert master.restart_epoch() == e0 + 1

    # observer that is not a member sees EXIT when all leases lapse
    observer = ElasticManager(port=master.port, np=2, node_id=9,
                              heartbeat_interval=0.1, lease_ttl=0.8)
    master.exit(completed=True)
    time.sleep(1.0)
    assert observer.watch() == ElasticStatus.EXIT


def test_elastic_scale_out():
    """A node joining later flips membership back to HOLD at the larger
    expectation (reference manager.py scale-out path)."""
    master = ElasticManager(port=0, np=2, node_id=0, is_master=True,
                            heartbeat_interval=0.1, lease_ttl=0.8)
    master.register()
    time.sleep(0.2)
    assert master.watch() == ElasticStatus.RESTART  # 1 of 2 present
    joiner = ElasticManager(port=master.port, np=2, node_id=1,
                            heartbeat_interval=0.1, lease_ttl=0.8)
    joiner.register()
    time.sleep(0.3)
    assert master.watch() == ElasticStatus.HOLD
    joiner.exit()
    master.exit()


def test_launch_elastic_restart_e2e(tmp_path):
    """Worker rank 0 crashes on its first life; the launcher relaunches
    the pod and the second life succeeds (reference elastic restart)."""
    port = _free_port()
    log_dir = tmp_path / "logs"
    worker = tmp_path / "crashy.py"
    worker.write_text(
        "import os, sys\n"
        "marker = sys.argv[1] + '/crashed_once'\n"
        "rank = os.environ.get('PADDLE_TRAINER_ID', '0')\n"
        "if rank == '0' and not os.path.exists(marker):\n"
        "    open(marker, 'w').write('x')\n"
        "    sys.exit(17)\n"
        "open(sys.argv[1] + f'/ok.{rank}', 'w').write('done')\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--master", f"127.0.0.1:{port}",
         "--nnodes", "1", "--nproc_per_node", "2",
         "--log_dir", str(log_dir), "--max_restart", "2",
         str(worker), str(tmp_path)],
        env=env, cwd=str(REPO), capture_output=True, text=True,
        timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert (tmp_path / "crashed_once").exists()
    assert (tmp_path / "ok.0").exists()
    assert (tmp_path / "ok.1").exists()
    assert "elastic restart" in r.stderr


def test_autotuner_candidates_and_prune():
    cands = default_candidates(8, num_layers=12)
    # every candidate factorizes the device count and divides the layers
    for c in cands:
        assert c["dp_degree"] * c["mp_degree"] * c["pp_degree"] == 8
        if c["pp_degree"] > 1:
            assert 12 % c["pp_degree"] == 0
    # pp=8 pruned (12 % 8 != 0)
    assert not any(c["pp_degree"] == 8 for c in cands)

    tuner = AutoTuner(num_devices=8, num_layers=12,
                      memory_limit_gb=1.0, model_params=500_000_000)
    kept = tuner.prune()
    # 500M params * 14B = 7GB: only shards >= 7 fit in 1GB
    for c in kept:
        assert c["mp_degree"] * c["pp_degree"] >= 7


def test_autotuner_search_picks_best_and_records_failures():
    tuner = AutoTuner(candidates=[
        {"mp_degree": 1, "pp_degree": 1},
        {"mp_degree": 2, "pp_degree": 1},
        {"mp_degree": 4, "pp_degree": 1},
        {"mp_degree": 8, "pp_degree": 1},
    ])

    def trial(cfg):
        if cfg["mp_degree"] == 8:
            raise MemoryError("OOM")
        if cfg["mp_degree"] == 4:
            return None  # skipped
        return 10.0 / cfg["mp_degree"]  # mp=2 is fastest

    best = tuner.tune(trial)
    assert best["mp_degree"] == 2
    hist = tuner.history()
    assert any("error" in h for h in hist)
    costs = [h["cost"] for h in hist if "cost" in h]
    assert len(costs) == 2

    import json
    import tempfile
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        path = f.name
    tuner.save_history(path)
    with open(path) as f:
        assert json.load(f) == hist
