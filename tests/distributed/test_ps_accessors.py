"""PS accessors (server-side optimizer rules) + cross-process rpc PS.

Reference model: paddle/fluid/distributed/ps/table/sparse_sgd_rule.h
(naive/adagrad/adam) applied per push; test/dist: subprocess cluster.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import numpy as np

from paddle_tpu.distributed.ps import (AdagradRule, AdamRule, PSServer,
                                       PSWorker, SGDRule)

REPO = Path(__file__).resolve().parent.parent.parent
WORKER = Path(__file__).resolve().parent / "ps_rpc_worker.py"


def test_adagrad_accessor_matches_numpy():
    rule = AdagradRule(lr=0.1)
    state = rule.init_state((3,))
    v = np.ones(3, np.float32)
    g = np.array([1.0, 2.0, 0.5], np.float32)
    v1 = rule.apply(v, g, state)
    np.testing.assert_allclose(v1, 1.0 - 0.1 * g / (np.abs(g) + 1e-8),
                               rtol=1e-5)
    # second apply accumulates g^2
    v2 = rule.apply(v1, g, state)
    np.testing.assert_allclose(
        v2, v1 - 0.1 * g / (np.sqrt(2 * g * g) + 1e-8), rtol=1e-5)


def test_adam_accessor_matches_torch():
    import torch

    rule = AdamRule(lr=0.01)
    state = rule.init_state((4,))
    v = np.zeros(4, np.float32)
    tp = torch.nn.Parameter(torch.zeros(4))
    topt = torch.optim.Adam([tp], lr=0.01)
    rng = np.random.default_rng(0)
    for _ in range(5):
        g = rng.standard_normal(4).astype(np.float32)
        v = rule.apply(v, g, state)
        tp.grad = torch.tensor(g)
        topt.step()
    np.testing.assert_allclose(v, tp.detach().numpy(), atol=1e-6)


def test_server_side_accessor_in_tables():
    server = PSServer(use_store=False)
    server.add_dense_table("d", (2,), lr=0.1, accessor="adam")
    server.add_sparse_table("s", 2, lr=0.1, accessor="adagrad")
    w = PSWorker(server)
    w.push_dense_grad("d", np.ones(2, np.float32))
    d = w.pull_dense("d")
    assert (d < 0).all()  # adam moved against the gradient
    w.push_sparse_grad("s", [7], np.ones((1, 2), np.float32))
    s0 = w.pull_sparse("s", [7])
    w.push_sparse_grad("s", [7], np.ones((1, 2), np.float32))
    s1 = w.pull_sparse("s", [7])
    assert (s1 < s0).all()


def test_sgd_rule_plain():
    rule = SGDRule(lr=0.5)
    v = rule.apply(np.ones(2, np.float32),
                   np.array([1.0, -1.0], np.float32),
                   rule.init_state((2,)))
    np.testing.assert_allclose(v, [0.5, 1.5])


def test_concurrent_pushes_not_lost():
    """Regression: table updates are serialized under the rpc thread
    pool — concurrent sparse pushes to a fresh row must all land."""
    import threading

    server = PSServer(use_store=False)
    server.add_dense_table("d", (1,), lr=1.0, accessor="sgd")
    server.add_sparse_table("s", 1, lr=1.0, accessor="sgd",
                            )
    server.tables["s"].initializer = lambda: np.zeros(1, np.float32)
    n_threads, n_push = 8, 50

    def hammer():
        w = PSWorker(server)
        for _ in range(n_push):
            w.push_dense_grad("d", np.ones(1, np.float32))
            w.push_sparse_grad("s", [100], np.ones((1, 1), np.float32))

    ts = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    total = n_threads * n_push
    np.testing.assert_allclose(server.tables["d"].value, [-total])
    np.testing.assert_allclose(server.tables["s"].rows[100], [-total])


def test_direct_mode_async_push_and_store_error():
    server = PSServer(use_store=False)
    server.add_dense_table("d", (2,), lr=0.5)
    w = PSWorker(server)
    fut = w.push_dense_grad("d", np.ones(2, np.float32), sync=False)
    assert fut.done()
    fut.wait()
    np.testing.assert_allclose(w.pull_dense("d"), [-0.5, -0.5])
    import pytest as _pytest
    with _pytest.raises(RuntimeError, match="use_store=False"):
        server.handle_once("k")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_ps_over_rpc_three_processes(tmp_path):
    port = _free_port()
    procs = []
    for rank in range(3):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get(
            "PYTHONPATH", "")
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINERS_NUM"] = "3"
        env["PADDLE_MASTER"] = f"127.0.0.1:{port}"
        procs.append(subprocess.Popen(
            [sys.executable, str(WORKER), str(tmp_path)],
            env=env, cwd=str(REPO), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    for rank, p in enumerate(procs):
        outp, _ = p.communicate(timeout=300)
        assert p.returncode == 0, f"rank {rank} failed:\n{outp[-4000:]}"
    assert (tmp_path / "ps_ok.server").exists()
    assert (tmp_path / "ps_ok.1").exists()
    assert (tmp_path / "ps_ok.2").exists()
