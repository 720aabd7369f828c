"""Zero-cold-start control plane (ISSUE 12): persistent AOT compile
cache (serving/aot_cache.py), warmup gating, and the SLO-weighted
multi-replica router (serving/router.py).

Acceptance pins: a warm on-disk cache serves a fresh jit entry point
with ZERO XLA compiles and bit-identical outputs; corrupt entries
quarantine to ``*.corrupt-N`` and recompile (never a wrong
executable); ``submit()`` during WARMING raises ``NotReadyError``
(same contract as DRAINING) and ``warmup()`` flips WARMING -> READY
after precompiling the bucket ladder + decode step; the router
weights placement by health, refuses non-READY replicas,
redistributes drains with zero dropped requests, and fails over dead
replicas such that every request lands EXACTLY once with the correct
terminal status; ``FLAGS_serving_aot_cache=0`` /
``FLAGS_serving_router=0`` revert byte-for-byte with counter silence;
compile-seconds-saved bills per request without breaking the PR 9
closure property.
"""

import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import deferred
from paddle_tpu.models import Llama, LlamaConfig
from paddle_tpu.profiler import metrics
from paddle_tpu.serving import (Lifecycle, NoReplicaAvailable,
                                NotReadyError, Router, ServingEngine,
                                aot_cache)
from paddle_tpu.testing import faults


@pytest.fixture(autouse=True)
def _no_trace_pollution():
    """Untraced by default (the test_accounting convention) — the one
    span test re-enables tracing itself."""
    saved = paddle.get_flags(["FLAGS_trace_enable"])
    paddle.set_flags({"FLAGS_trace_enable": False})
    yield
    paddle.set_flags(saved)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def aot_dir(tmp_path):
    """Arm the AOT cache at a private store; disarm afterward."""
    saved = paddle.get_flags(["FLAGS_serving_aot_cache",
                              "FLAGS_aot_cache_dir"])
    aot_cache.configure(str(tmp_path))
    paddle.set_flags({"FLAGS_serving_aot_cache": True})
    yield str(tmp_path)
    paddle.set_flags(saved)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


def _fresh_model():
    """A NEW model instance: fresh (uncompiled) paged jit entry points,
    the in-process stand-in for a fresh process."""
    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("bucket_cap", 16)
    kw.setdefault("background", False)
    return ServingEngine(model, **kw)


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, (s,)).astype("int64") for s in sizes]


def _aot(name):
    return metrics.snapshot("jit.aot.")[f"jit.aot.{name}"]


def _compiles():
    return metrics.snapshot()["xla.compile.count"]


# -- AOT compile cache ------------------------------------------------------

def test_aot_roundtrip_store_then_hit_bitwise(aot_dir):
    """A wrapped jitted fn stores on first compile; a FRESH wrapper
    (fresh process stand-in) loads it with zero backend compiles and
    bit-identical outputs, billing the saved compile seconds."""
    import jax
    import jax.numpy as jnp

    def f(x, y):
        return jnp.tanh(x @ y) * 3.0 + x.sum()

    x = jnp.linspace(0.0, 1.0, 64).reshape(8, 8)
    y = jnp.linspace(1.0, 2.0, 64).reshape(8, 8)
    h0, m0, s0 = _aot("hits"), _aot("misses"), _aot("stores")
    w1 = aot_cache.wrap(jax.jit(f), tag="test.roundtrip")
    out1 = np.asarray(w1(x, y))
    assert _aot("misses") == m0 + 1 and _aot("stores") == s0 + 1
    assert glob.glob(os.path.join(aot_dir, "*.aotx"))
    saved0 = aot_cache.thread_saved_seconds()
    w2 = aot_cache.wrap(jax.jit(f), tag="test.roundtrip")
    c0 = _compiles()
    out2 = np.asarray(w2(x, y))
    assert _compiles() == c0, "a cache hit must not compile"
    assert _aot("hits") == h0 + 1
    assert aot_cache.thread_saved_seconds() > saved0
    assert out1.tobytes() == out2.tobytes()
    # warm path: the second call dispatches straight from the table
    out3 = np.asarray(w2(x, y))
    assert out1.tobytes() == out3.tobytes()


@pytest.mark.filterwarnings("error::UserWarning")
def test_aot_donated_program_roundtrips_and_still_consumes_its_input(
        aot_dir):
    """A program with donated arguments stores and loads like any other,
    and the LOADED executable still takes its input's buffer: the array
    handed in is deleted, the result is bit-identical to the compiled
    one's, and what was not donated stays alive."""
    import jax
    import jax.numpy as jnp

    def f(pools, dst, rows):
        return [p.at[dst].set(r) for p, r in zip(pools, rows)]

    def args():
        return ([jnp.zeros((6, 4, 8)), jnp.ones((6, 4, 8))],
                jnp.asarray([4, 1], jnp.int32),
                [jnp.full((2, 4, 8), 7.0), jnp.full((2, 4, 8), 9.0)])

    h0, s0 = _aot("hits"), _aot("stores")
    w1 = aot_cache.wrap(jax.jit(f, donate_argnums=(0,)), tag="test.donate")
    pools, dst, rows = args()
    out1 = [np.array(o) for o in w1(pools, dst, rows)]
    assert _aot("stores") == s0 + 1
    assert all(p.is_deleted() for p in pools)
    w2 = aot_cache.wrap(jax.jit(f, donate_argnums=(0,)), tag="test.donate")
    c0 = _compiles()
    for _ in range(2):  # the load, then the warm table
        pools, dst, rows = args()
        out2 = w2(pools, dst, rows)
        assert all(p.is_deleted() for p in pools)
        assert not dst.is_deleted() and not rows[0].is_deleted()
        for a, b in zip(out1, out2):
            assert a.tobytes() == np.asarray(b).tobytes()
    assert _compiles() == c0 and _aot("hits") == h0 + 1
    assert out1[0][4, 0, 0] == 7.0 and out1[1][1, 0, 0] == 9.0


@pytest.mark.filterwarnings("error::UserWarning")
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["plain", "int8"])
def test_aot_loaded_serving_programs_write_the_pools_in_place(
        aot_dir, kv_dtype):
    """A second model's programs come from the store: each still
    consumes the pools it is handed (every call counted as donated,
    none as copied) and the tokens are the first model's."""
    def serve():
        eng = _engine(_fresh_model(), kv_cache_dtype=kv_dtype)
        handed_in = eng.cache.pool_arrays()
        k0 = metrics.snapshot("serving.kv.")
        hs = [eng.submit(p, max_new_tokens=5) for p in _prompts(27, [6, 11])]
        eng.run_until_idle()
        k1 = metrics.snapshot("serving.kv.")
        assert all(a.is_deleted() for a in handed_in)
        assert k1["serving.kv.copied_calls"] == k0["serving.kv.copied_calls"]
        assert k1["serving.kv.donated_calls"] \
            >= k0["serving.kv.donated_calls"] + 2 + 4
        eng.close()
        return [h.tokens() for h in hs]

    first = serve()
    h0 = _aot("hits")
    assert serve() == first
    assert _aot("hits") >= h0 + 2  # a prefill bucket and the decode step


@pytest.mark.parametrize("damage", ["truncate", "bitflip", "garbage"])
def test_aot_corruption_quarantines_and_recompiles(aot_dir, damage):
    """Truncated / bit-flipped / garbage entries quarantine to
    ``*.corrupt-N`` and fall back to a normal compile that re-stores a
    fresh entry — outputs bit-identical, never a wrong executable."""
    import jax
    import jax.numpy as jnp

    def f(x):
        return (x * 2.0 + 1.0).cumsum()

    x = jnp.linspace(0.0, 3.0, 32)
    ref = np.asarray(aot_cache.wrap(jax.jit(f), tag=damage)(x))
    [path] = glob.glob(os.path.join(aot_dir, "*.aotx"))
    raw = open(path, "rb").read()
    if damage == "truncate":
        open(path, "wb").write(raw[:len(raw) // 2])
    elif damage == "bitflip":
        b = bytearray(raw)
        b[len(b) // 2] ^= 0xFF
        open(path, "wb").write(bytes(b))
    else:
        open(path, "wb").write(b"not an executable at all")
    q0, s0 = _aot("quarantined"), _aot("stores")
    out = np.asarray(aot_cache.wrap(jax.jit(f), tag=damage)(x))
    assert out.tobytes() == ref.tobytes()
    assert _aot("quarantined") == q0 + 1
    assert glob.glob(os.path.join(aot_dir, "*.corrupt-*"))
    # the slot re-stored: a THIRD process would hit cleanly
    assert _aot("stores") == s0 + 1
    assert len(glob.glob(os.path.join(aot_dir, "*.aotx"))) == 1


def test_aot_disarmed_counter_silent_and_diskless(tmp_path):
    """FLAGS_serving_aot_cache=0 (and the no-dir default) forward
    straight to jax.jit: no files, every jit.aot.* counter silent."""
    import jax
    import jax.numpy as jnp

    saved = paddle.get_flags(["FLAGS_serving_aot_cache",
                              "FLAGS_aot_cache_dir"])
    try:
        paddle.set_flags({"FLAGS_serving_aot_cache": False,
                          "FLAGS_aot_cache_dir": str(tmp_path)})
        before = metrics.snapshot("jit.aot.")
        w = aot_cache.wrap(jax.jit(lambda x: x + 1.0), tag="silent")
        np.asarray(w(jnp.ones((4,))))
        assert metrics.snapshot("jit.aot.") == before
        assert os.listdir(tmp_path) == []
        # dir empty (the production default) is equally silent
        paddle.set_flags({"FLAGS_serving_aot_cache": True,
                          "FLAGS_aot_cache_dir": ""})
        np.asarray(w(jnp.ones((4,))))
        assert metrics.snapshot("jit.aot.") == before
    finally:
        paddle.set_flags(saved)


def test_deferred_chain_programs_ride_the_cache(aot_dir):
    """Deferred-chain programs (the passes/v1|v2 jit namespaces) store
    and re-load through the same cache: clearing the in-memory chain
    cache forces the next flush to disk — a hit, zero compiles, and
    bitwise-identical chain results."""
    def chain():
        t = paddle.to_tensor(
            np.linspace(0.1, 1.0, 16).astype("float32"))
        y = t
        for _ in range(9):
            y = y * 1.5 + 0.25
        return y.numpy()

    a = chain()
    h0 = _aot("hits")
    with deferred._CACHE_LOCK:
        deferred._JIT_CACHE.clear()
    c0 = _compiles()
    b = chain()
    assert _aot("hits") == h0 + 1
    assert _compiles() == c0
    assert a.tobytes() == b.tobytes()


# -- warmup gating ----------------------------------------------------------

def test_submit_during_warming_raises_not_ready():
    """WARMING rejects submits exactly like DRAINING — /readyz and
    submit semantics agree, and no request can be billed the cold
    compiles warmup() owes."""
    eng = _engine(_fresh_model(), ready=False)
    assert eng.lifecycle == Lifecycle.WARMING
    with pytest.raises(NotReadyError, match="WARMING"):
        eng.submit(_prompts(1, [6])[0], max_new_tokens=2)
    eng.close()


def test_warmup_flips_ready_and_first_request_never_compiles(aot_dir):
    """warmup() precompiles the full bucket ladder + decode step and
    flips WARMING -> READY; the first live request then runs with ZERO
    XLA compiles (cold OR warm cache) — the cold-start gate."""
    wp0 = metrics.snapshot("serving.")["serving.warmup.programs"]
    eng = _engine(_fresh_model(), ready=False)
    n = eng.warmup()
    assert eng.lifecycle == Lifecycle.READY
    assert n >= 3  # >=2 prefill buckets + the decode program
    assert metrics.snapshot("serving.")["serving.warmup.programs"] \
        == wp0 + n
    c0 = _compiles()
    h = eng.submit(_prompts(2, [6])[0], max_new_tokens=4)
    eng.run_until_idle()
    assert h.status == "DONE" and len(h.tokens()) == 4
    assert _compiles() == c0, \
        "a warmed engine must serve its first request compile-free"
    eng.close()
    # warm boot: a FRESH model (fresh jit objects) warms from disk —
    # still zero compiles at the first request. In-process, the first
    # program may fingerprint to a warm-trace variant (dispatch's
    # staged-call form differs from a cold process's inline trace —
    # at most ONE extra entry; tools/router_gate.py pins the true
    # cross-process case at exactly zero misses)
    h0, m0 = _aot("hits"), _aot("misses")
    eng2 = _engine(_fresh_model(), ready=False)
    eng2.warmup()
    assert _aot("misses") <= m0 + 1
    assert _aot("hits") >= h0 + n - 1
    c0 = _compiles()
    h = eng2.submit(_prompts(2, [6])[0], max_new_tokens=4)
    eng2.run_until_idle()
    assert h.status == "DONE" and _compiles() == c0
    eng2.close()


@pytest.mark.parametrize("cap, programs", [
    (16, [8, 16]),                  # the ladder ends at the cap
    (32, [8, 16, 32]),              # cap = max_seq_len: every bucket
    (0, [8, 16, 24, 32]),           # no bucketing: every block multiple
])
def test_warmup_compiles_the_ladder_to_the_cap(cap, programs):
    """Past the cap a prompt pads to its own block multiple, one program
    a length (192 at the flag's cap of 1024 under a max_seq_len of
    4096): warmup() leaves those to the prompt that needs one."""
    eng = _engine(_fresh_model(), ready=False, bucket_cap=cap)
    assert eng.warmup() == len(programs) + 1      # + the decode program
    c0 = _compiles()
    h = eng.submit(_prompts(3, [programs[-1] - 2])[0], max_new_tokens=2)
    eng.run_until_idle()
    assert h.status == "DONE" and _compiles() == c0
    if cap == 16:
        # served all the same, compiled when it comes
        h = eng.submit(_prompts(4, [20])[0], max_new_tokens=2)
        eng.run_until_idle()
        assert h.status == "DONE" and _compiles() > c0
    eng.close()


def test_warmup_raises_past_draining(model):
    eng = _engine(model)
    eng.drain()
    with pytest.raises(RuntimeError, match="CLOSED"):
        eng.warmup()
    eng.close()


def test_aot_savings_billed_to_request_and_closure_holds(aot_dir):
    """An UNWARMED engine over a warm store: the first request's
    prefill/decode dispatches HIT the cache, so its CostReport carries
    aot_saved_us > 0 — while the PR 9 closure (attributed + compile +
    idle == step) still holds exactly (savings are an informational
    axis, never part of the sum)."""
    # populate the store
    eng = _engine(_fresh_model(), ready=False)
    eng.warmup()
    eng.close()
    # fresh engine, NO warmup: requests pay the (cheap) loads and get
    # credited the avoided compiles
    eng2 = _engine(_fresh_model())
    h = eng2.submit(_prompts(3, [6])[0], max_new_tokens=4)
    eng2.run_until_idle()
    assert h.status == "DONE"
    cost = h.cost()
    assert cost.aot_saved_us > 0.0
    assert cost.aot_saved_us == pytest.approx(
        sum(e["aot_saved_us"] for e in
            eng2.scheduler.accounting.step_log))
    for e in eng2.scheduler.accounting.step_log:
        assert e["step_us"] == pytest.approx(
            e["attributed_us"] + e["compile_us"] + e["idle_us"])
    rep = eng2.accounting.engine_report()
    assert rep["aot_saved_us"] == pytest.approx(cost.aot_saved_us)
    eng2.close()


# -- the router -------------------------------------------------------------

def _two_replicas(model, **kw):
    e1 = _engine(model, **kw)
    e2 = _engine(model, **kw)
    r = Router()
    r.add_replica("r1", engine=e1)
    r.add_replica("r2", engine=e2)
    return r, e1, e2


def test_router_balances_load_and_counts(model):
    """Equal healthy replicas round-robin via the inflight damping;
    every request lands exactly once, router.routed counts each."""
    r, e1, e2 = _two_replicas(model)
    routed0 = metrics.snapshot("router.")["router.routed"]
    hs = [r.submit(p, max_new_tokens=3)
          for p in _prompts(4, [5, 7, 6, 9])]
    assert {h.replica_id for h in hs} == {"r1", "r2"}
    e1.run_until_idle()
    e2.run_until_idle()
    assert all(h.status == "DONE" and len(h.tokens()) == 3 for h in hs)
    assert metrics.snapshot("router.")["router.routed"] == routed0 + 4
    done = [q for eng in (e1, e2) for q in eng.scheduler.finished.values()
            if q.status == "DONE"]
    assert len(done) == 4  # exactly once across the fleet
    e1.close()
    e2.close()


def test_router_refuses_not_ready_and_drain_redistributes(model):
    """A drained replica finishes its in-flight work (zero dropped,
    the PR 11 contract) while the router lands every new request on
    the survivors."""
    r, e1, e2 = _two_replicas(model, background=True)
    inflight = [r.submit(p, max_new_tokens=4)
                for p in _prompts(5, [6, 8])]
    r.drain("r1", timeout=120)
    # zero dropped: whatever was on r1 completed DONE through the drain
    for h in inflight:
        assert h.result(timeout=120) is not None
        assert h.status == "DONE"
    after = [r.submit(p, max_new_tokens=2)
             for p in _prompts(6, [5, 6, 7])]
    assert all(h.replica_id == "r2" for h in after)
    for h in after:
        assert h.result(timeout=120) is not None and h.status == "DONE"
    e1.close()
    e2.close()


def test_router_retries_failed_submit_on_next_best(model):
    """A submit-site fault on one replica moves the request to the
    next-best (counted router.retried); it still lands exactly once."""
    r, e1, e2 = _two_replicas(model)
    snap0 = metrics.snapshot("router.")
    # whichever replica the router tries FIRST will refuse
    with faults.inject("router.submit", nth=1, count=1):
        h = r.submit(_prompts(7, [6])[0], max_new_tokens=3)
    e1.run_until_idle()
    e2.run_until_idle()
    assert h.status == "DONE" and len(h.tokens()) == 3
    snap1 = metrics.snapshot("router.")
    assert snap1["router.retried"] == snap0["router.retried"] + 1
    assert snap1["router.routed"] == snap0["router.routed"] + 1
    done = [q for eng in (e1, e2) for q in eng.scheduler.finished.values()
            if q.status == "DONE"]
    assert len(done) == 1
    e1.close()
    e2.close()


def test_router_failover_matrix_exactly_once(model):
    """Replica death mid-flight: the victim's requests terminate ERROR
    on the dead replica and the router re-submits each to a survivor —
    every request completes EXACTLY once, tokens bit-identical to an
    undisturbed run, correct terminal status, failovers counted."""
    prompts = _prompts(8, [7, 5, 9])
    ref_eng = _engine(model)
    refs = []
    for p in prompts:
        h = ref_eng.submit(p, max_new_tokens=5)
        ref_eng.run_until_idle()
        refs.append(h.tokens())
    ref_eng.close()

    r, e1, e2 = _two_replicas(model, background=True)
    # r1's driver waits for its engine's lock (re-entrant for this
    # thread's submits) until the death is in place: however fast a
    # step is, r1 finishes none of its requests first
    with e1._lock:
        hs = [r.submit(p, max_new_tokens=5) for p in prompts]
        victims = [h for h in hs if h.replica_id == "r1"]
        assert victims, "placement must have used r1"
        # kill r1 the way a crashed device manifests: its driver dies
        e1._sched.step = lambda: (_ for _ in ()).throw(
            RuntimeError("injected replica death"))
    f0 = metrics.snapshot("router.")["router.failover"]
    outs = [h.result(timeout=120) for h in hs]
    assert all(h.status == "DONE" for h in hs)
    assert [list(o) for o in outs] == [list(t) for t in refs]
    assert all(h.replica_id == "r2" for h in victims)
    assert metrics.snapshot("router.")["router.failover"] \
        == f0 + len(victims)
    # exactly once: every DONE lives on exactly one engine; the dead
    # replica holds only ERROR terminals for the failed-over rids
    done = [q for eng in (e1, e2) for q in eng.scheduler.finished.values()
            if q.status == "DONE"]
    assert len(done) == len(prompts)
    try:
        e1.close()
    except RuntimeError:
        pass
    e2.close()


def test_router_gives_up_loud_when_no_replica_ready(model):
    r, e1, e2 = _two_replicas(model)
    e1.drain()
    e2.drain()
    rej0 = metrics.snapshot("router.")["router.rejected"]
    with pytest.raises(NoReplicaAvailable):
        r.submit(_prompts(9, [5])[0], max_new_tokens=2)
    assert metrics.snapshot("router.")["router.rejected"] == rej0 + 1
    e1.close()
    e2.close()


def test_router_weights_off_stale_heartbeat(model):
    """Store discovery binds registry payloads: a replica whose
    heartbeat went silent decays to zero weight (fleet.health_score
    freshness), so placement shifts off it BEFORE it formally ages
    out — telemetry as a control loop."""
    r, e1, e2 = _two_replicas(model)
    now = time.time()
    r._replicas["r1"].member = {"replica_id": "r1", "url": "x",
                                "state": "READY", "ttl_s": 3.0,
                                "heartbeat_ts": now - 10.0}  # silent
    r._replicas["r2"].member = {"replica_id": "r2", "url": "x",
                                "state": "READY", "ttl_s": 3.0,
                                "heartbeat_ts": now}
    assert r._replicas["r1"].health() == 0.0
    hs = [r.submit(p, max_new_tokens=2) for p in _prompts(10, [5, 6])]
    assert all(h.replica_id == "r2" for h in hs)
    e2.run_until_idle()
    assert all(h.status == "DONE" for h in hs)
    e1.close()
    e2.close()


def test_router_disarmed_passthrough_counter_silent(model):
    """FLAGS_serving_router=0 (read at construction): Router.submit is
    the primary engine's plain submit — identical handle type, zero
    router.* counter movement."""
    saved = paddle.get_flags(["FLAGS_serving_router"])
    try:
        paddle.set_flags({"FLAGS_serving_router": False})
        r, e1, e2 = _two_replicas(model)
    finally:
        paddle.set_flags(saved)
    before = metrics.snapshot("router.")
    h = r.submit(_prompts(11, [6])[0], max_new_tokens=3)
    from paddle_tpu.serving import RequestHandle
    assert isinstance(h, RequestHandle)  # not a RoutedHandle
    e1.run_until_idle()
    assert h.status == "DONE"
    assert metrics.snapshot("router.") == before
    assert len(e2.scheduler.finished) == 0  # primary-only
    e1.close()
    e2.close()


def test_route_span_stitched_into_request_trace(model):
    """The serving.route decision rides the request's OWN trace: one
    trace reads route -> queue -> prefill -> decode -> terminal."""
    from paddle_tpu.profiler import tracing

    paddle.set_flags({"FLAGS_trace_enable": True,
                      "FLAGS_trace_sample": 1.0})
    r, e1, e2 = _two_replicas(model)
    h = r.submit(_prompts(12, [6])[0], max_new_tokens=3)
    e1.run_until_idle()
    e2.run_until_idle()
    assert h.status == "DONE"
    names = {s["name"] for s in tracing.get_trace(h.trace_id)}
    assert "serving.route" in names
    assert "serving.request" in names
    e1.close()
    e2.close()
