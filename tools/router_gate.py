"""Zero-cold-start gate: the AOT compile cache + warmup + router
control plane (ISSUE 12) through five pass/fail checks, in order of
importance:

  1. zero-cold-start — a SECOND PROCESS pointed at a warm on-disk AOT
     cache (serving/aot_cache.py) warms up with ZERO cache misses and
     serves its first request with ZERO XLA compilations, pinned via
     the existing ``xla.compile.count`` / ``xla.compile.seconds``
     metrics (profiler.metrics' jax.monitoring listener) — and the
     warm process's total compile seconds collapse vs the cold one;
  2. traffic-shift — the router measurably shifts placement off a
     health-degraded replica (its registry heartbeat killed via
     ``testing/faults``, the fleet_gate injection): after the decay
     window every new request lands on the healthy replica;
  3. drain-redistribute — draining one replica through the router
     completes its in-flight requests (ZERO dropped, all DONE) while
     every subsequent submit lands on the survivor;
  4. failover — a replica dying mid-flight fails its requests over to
     the next-best replica: every request completes EXACTLY once,
     DONE, with ``router.failover`` counting each move;
  5. disarmed — ``FLAGS_serving_aot_cache=0`` and
     ``FLAGS_serving_router=0`` are counter-silent byte-for-byte
     reverts (no ``jit.aot.*`` / ``router.*`` movement, no store
     files).

Exit 0 on pass, 1 on fail; one line per check. Runs under
JAX_PLATFORMS=cpu (tier-1, like tests/framework/test_router.py);
wired into tools/suite_gate.py beside the serving/fleet gates, and
appends a ``router_gate`` entry (cold/warm compile seconds, hit
counts, check bits) to the continuous-bench ledger
(tools/bench_ledger.py).
"""

import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

TTL_S = float(os.environ.get("ROUTER_GATE_TTL_S", "3.0"))
CHILD_TIMEOUT_S = float(os.environ.get("ROUTER_GATE_CHILD_TIMEOUT_S",
                                       "300"))

# the child process of check 1: boot an engine through warmup() against
# the shared store, serve ONE request, report the compile/aot counters.
# The measurement window for "first request" opens AFTER warmup — the
# boot contract — but the warm process must ALSO show zero cache misses
# (its warmup loaded every program from disk).
_CHILD = r"""
import json, sys
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import Llama, LlamaConfig
from paddle_tpu.serving import ServingEngine, aot_cache
from paddle_tpu.profiler import metrics

aot_cache.configure(sys.argv[1])
paddle.seed(0)
m = Llama(LlamaConfig.tiny()); m.eval()
eng = ServingEngine(m, max_batch=2, block_size=8, max_seq_len=32,
                    temperature=0.0, bucket_cap=16, background=False,
                    ready=False)
programs = eng.warmup()
snap = metrics.snapshot()
c0 = snap["xla.compile.count"]
h = eng.submit(np.arange(6), max_new_tokens=4)
eng.run_until_idle()
snap1 = metrics.snapshot()
out = {"programs": programs,
       "tokens": [int(t) for t in h.tokens()],
       "status": h.status,
       "request_compiles": snap1["xla.compile.count"] - c0,
       "total_compiles": snap1["xla.compile.count"],
       "compile_s": snap1["xla.compile.seconds"]["sum"],
       "aot_hits": snap1["jit.aot.hits"],
       "aot_misses": snap1["jit.aot.misses"],
       "aot_stores": snap1["jit.aot.stores"]}
eng.close()
print("ROUTER_GATE_JSON " + json.dumps(out))
"""


def _run_child(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", _CHILD, cache_dir],
        capture_output=True, text=True, timeout=CHILD_TIMEOUT_S,
        env=env, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    for line in p.stdout.splitlines():
        if line.startswith("ROUTER_GATE_JSON "):
            return json.loads(line[len("ROUTER_GATE_JSON "):])
    raise RuntimeError(
        f"child produced no report (rc={p.returncode}):\n"
        f"{p.stdout[-2000:]}\n{p.stderr[-2000:]}")


def check_zero_cold_start():
    with tempfile.TemporaryDirectory() as d:
        cold = _run_child(d)
        warm = _run_child(d)
    ok = (cold["status"] == "DONE" and warm["status"] == "DONE"
          and warm["tokens"] == cold["tokens"]
          and cold["aot_stores"] >= 3
          and warm["aot_misses"] == 0
          and warm["aot_hits"] >= cold["aot_stores"]
          and warm["request_compiles"] == 0
          and warm["compile_s"] < 0.5 * max(cold["compile_s"], 1e-9))
    print(f"[router-gate] zero-cold-start: cold compile "
          f"{cold['compile_s']:.2f}s/{cold['total_compiles']} compiles "
          f"-> warm {warm['compile_s']:.2f}s/{warm['total_compiles']} "
          f"(misses={warm['aot_misses']} want 0, "
          f"hits={warm['aot_hits']}, first-request "
          f"compiles={warm['request_compiles']} want 0, "
          f"bit-identical={warm['tokens'] == cold['tokens']}) "
          f"{'PASS' if ok else 'FAIL'}")
    return ok, cold, warm


def _model():
    import paddle_tpu as paddle
    from paddle_tpu.models import Llama, LlamaConfig

    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


def _engine(model, **kw):
    from paddle_tpu.serving import ServingEngine

    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("bucket_cap", 32)
    kw.setdefault("background", False)
    return ServingEngine(model, **kw)


def _prompts(seed, sizes):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, (s,)).astype("int64") for s in sizes]


def _backdate_heartbeat(store, replica_id, age_s):
    """Rewrite a replica's registry entry with a heartbeat_ts ``age_s``
    in the past — the deterministic form of "its heartbeat died a
    while ago". Call only with the replica's beat loop already dead
    (fault-armed), or the next beat would overwrite the back-dated
    entry."""
    import json

    from paddle_tpu.profiler import fleet

    for p in fleet.read_members(store):
        if str(p.get("replica_id")) == replica_id:
            p["heartbeat_ts"] = time.time() - age_s
            store.set(fleet.MEMBER_KEY_FMT.format(p["slot"]),
                      json.dumps(p))
            return
    raise RuntimeError(f"replica {replica_id} not in the registry")


def check_traffic_shift(model):
    """Kill one replica's registry heartbeat; once its freshness is
    gone the router must place everything on the healthy one.

    The decay is made DETERMINISTIC by advancing the heartbeat clock
    instead of racing real time: the fault stops future beats, one
    beat period of settling lets any in-flight beat land, then g2's
    registry entry is back-dated a full TTL — freshness (and so
    health) is exactly 0.0. The previous sleep-only version was
    timing-flaky at the decay margin (CHANGES.md PR 13 "Known"): a
    killed-but-still-freshish heartbeat could leave g2's decayed
    score above g1's inflight-damped rank for the later submits of
    the burst."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.serving import Router
    from paddle_tpu.testing import faults

    paddle.set_flags({"FLAGS_fleet_ttl_s": TTL_S})
    store = TCPStore(is_master=True)
    e1 = _engine(model)
    e2 = _engine(model)
    s1 = e1.serve_metrics(store=store, replica_id="g1")
    s2 = e2.serve_metrics(store=store, replica_id="g2")
    router = Router(store=store)
    router.add_replica("g1", engine=e1)
    router.add_replica("g2", engine=e2)
    router.refresh(force=True)
    before = [router.submit(p, max_new_tokens=2)
              for p in _prompts(3, [5, 6, 7, 5])]
    e1.run_until_idle()
    e2.run_until_idle()
    spread = {h.replica_id for h in before}
    faults.arm("fleet.heartbeat.g2", nth=1, count=10 ** 6)
    try:
        time.sleep(TTL_S / 3.0 + 0.2)  # any in-flight beat lands
        _backdate_heartbeat(store, "g2", TTL_S)
        router.refresh(force=True)
        h2 = router._replicas["g2"].health()
        h1 = router._replicas["g1"].health()
        after = [router.submit(p, max_new_tokens=2)
                 for p in _prompts(4, [5, 6, 7])]
        e1.run_until_idle()
        e2.run_until_idle()
    finally:
        faults.disarm("fleet.heartbeat.g2")
    landed = [h.replica_id for h in after]
    ok = (spread == {"g1", "g2"} and h2 == 0.0 and h2 < h1
          and all(r == "g1" for r in landed)
          and all(h.status == "DONE" for h in before + after))
    print(f"[router-gate] traffic-shift: balanced={sorted(spread)} "
          f"degraded g2 health {h2:.3f} (want 0.0) < g1 {h1:.3f}; "
          f"post-degrade placement={landed} (want all g1) "
          f"{'PASS' if ok else 'FAIL'}")
    for eng in (e1, e2):
        eng.close()
    return ok


def check_drain_redistributes(model):
    from paddle_tpu.serving import NotReadyError, Router

    e1 = _engine(model, background=True)
    e2 = _engine(model, background=True)
    router = Router()
    router.add_replica("d1", engine=e1)
    router.add_replica("d2", engine=e2)
    inflight = [router.submit(p, max_new_tokens=4)
                for p in _prompts(5, [6, 8, 7, 5])]
    router.drain("d1", timeout=120)
    dropped = sum(1 for h in inflight
                  if h.result(timeout=120) is None
                  or h.status != "DONE")
    after = [router.submit(p, max_new_tokens=2)
             for p in _prompts(6, [5, 6])]
    landed = [h.replica_id for h in after]
    done_after = all(h.result(timeout=120) is not None
                     and h.status == "DONE" for h in after)
    rejected = False
    try:
        e1.submit(_prompts(7, [5])[0], max_new_tokens=1)
    except NotReadyError:
        rejected = True
    ok = dropped == 0 and all(r == "d2" for r in landed) \
        and done_after and rejected
    print(f"[router-gate] drain-redistribute: dropped={dropped} "
          f"(want 0) post-drain placement={landed} (want all d2) "
          f"drained-replica-rejects={rejected} "
          f"{'PASS' if ok else 'FAIL'}")
    e1.close()
    e2.close()
    return ok


def check_failover(model):
    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import Router

    ref = _engine(model)
    prompts = _prompts(8, [7, 5])
    refs = []
    for p in prompts:
        h = ref.submit(p, max_new_tokens=5)
        ref.run_until_idle()
        refs.append(h.tokens())
    ref.close()

    e1 = _engine(model, background=True)
    e2 = _engine(model, background=True)
    router = Router()
    router.add_replica("f1", engine=e1)
    router.add_replica("f2", engine=e2)
    hs = [router.submit(p, max_new_tokens=5) for p in prompts]
    victims = [h for h in hs if h.replica_id == "f1"]
    f0 = metrics.snapshot("router.")["router.failover"]
    e1._sched.step = lambda: (_ for _ in ()).throw(
        RuntimeError("gate: injected replica death"))
    outs = [h.result(timeout=120) for h in hs]
    moved = metrics.snapshot("router.")["router.failover"] - f0
    done = [q for eng in (e1, e2)
            for q in eng.scheduler.finished.values()
            if q.status == "DONE"]
    ok = (len(victims) >= 1 and moved == len(victims)
          and all(h.status == "DONE" for h in hs)
          and [list(o) for o in outs] == [list(t) for t in refs]
          and len(done) == len(prompts))
    print(f"[router-gate] failover: victims={len(victims)} "
          f"moved={moved} exactly-once={len(done)}=={len(prompts)} "
          f"bit-identical={[list(o) for o in outs] == [list(t) for t in refs]} "
          f"{'PASS' if ok else 'FAIL'}")
    try:
        e1.close()
    except RuntimeError:
        pass
    e2.close()
    return ok


def check_disarmed(model):
    import paddle_tpu as paddle
    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import Router

    saved = paddle.get_flags(["FLAGS_serving_aot_cache",
                              "FLAGS_aot_cache_dir",
                              "FLAGS_serving_router"])
    with tempfile.TemporaryDirectory() as d:
        try:
            paddle.set_flags({"FLAGS_serving_aot_cache": False,
                              "FLAGS_aot_cache_dir": d,
                              "FLAGS_serving_router": False})
            before_aot = metrics.snapshot("jit.aot.")
            before_router = metrics.snapshot("router.")
            eng = _engine(model)
            router = Router()
            router.add_replica("s1", engine=eng)
            h = router.submit(_prompts(9, [6])[0], max_new_tokens=3)
            eng.run_until_idle()
            files = os.listdir(d)
            aot_silent = metrics.snapshot("jit.aot.") == before_aot
            router_silent = metrics.snapshot("router.") == before_router
            eng.close()
        finally:
            paddle.set_flags(saved)
    ok = h.status == "DONE" and aot_silent and router_silent \
        and files == []
    print(f"[router-gate] disarmed: aot-silent={aot_silent} "
          f"router-silent={router_silent} store-files={len(files)} "
          f"(want 0) {'PASS' if ok else 'FAIL'}")
    return ok


def main():
    ok1, cold, warm = check_zero_cold_start()
    model = _model()
    ok2 = check_traffic_shift(model)
    ok3 = check_drain_redistributes(model)
    ok4 = check_failover(model)
    ok5 = check_disarmed(model)
    ok = ok1 and ok2 and ok3 and ok4 and ok5
    try:
        import bench_ledger
        bench_ledger.append_entry("router_gate", {
            "cold_compile_s": round(cold["compile_s"], 3),
            "warm_compile_s": round(warm["compile_s"], 3),
            "warm_request_compiles": float(warm["request_compiles"]),
            "aot_warm_hits": float(warm["aot_hits"]),
            "router_shift_ok": 1.0 if ok2 else 0.0,
            "router_failover_ok": 1.0 if ok4 else 0.0})
        print(f"[router-gate] ledger: appended router_gate (cold "
              f"{cold['compile_s']:.2f}s -> warm "
              f"{warm['compile_s']:.2f}s)")
    except Exception as e:  # noqa: BLE001 — ledger trouble is advisory
        print(f"[router-gate] ledger append skipped "
              f"({type(e).__name__}: {e})")
    print(f"[router-gate] {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
