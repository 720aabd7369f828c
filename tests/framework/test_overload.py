"""Overload control plane (ISSUE 13, serving/overload.py +
core/resilience.CircuitBreaker + router breakers).

Pins the contract docs/SERVING.md "Overload control plane" documents:
provably-unmeetable deadlines fail fast at submit with a structured
``AdmissionRejected`` (never pay prefill for a corpse), pressure
watermarks shed lowest-priority/newest QUEUED requests to terminal
status ``SHED`` with a ``retry_after_s`` (blocks never allocated,
survivors greedy bit-identical to an uncontended run), the brownout
ladder walks stages edge-triggered with hysteresis, router circuit
breakers open after repeated submit failures and recover through a
half-open probe, and ``FLAGS_serving_admission=0`` /
``FLAGS_serving_brownout=0`` / ``FLAGS_router_breaker=0`` revert
byte-for-byte with counter silence.
"""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import resilience
from paddle_tpu.inference.paged import ContinuousBatchingEngine
from paddle_tpu.models import Llama, LlamaConfig
from paddle_tpu.profiler import alerts as alerts_mod
from paddle_tpu.profiler import metrics
from paddle_tpu.serving import (AdmissionRejected, NoReplicaAvailable,
                                QueueFullError, RequestStatus, Router,
                                ServingEngine, overload)
from paddle_tpu.testing import faults


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


@pytest.fixture()
def flags_guard():
    """Snapshot/restore every overload-plane flag a test may touch."""
    names = ["FLAGS_serving_admission", "FLAGS_serving_brownout",
             "FLAGS_router_breaker", "FLAGS_shed_min_queue",
             "FLAGS_shed_queue_frac", "FLAGS_shed_kv_frac",
             "FLAGS_shed_wait_s", "FLAGS_admission_optimism",
             "FLAGS_brownout_enter_steps", "FLAGS_brownout_exit_steps",
             "FLAGS_brownout_exit_pressure",
             "FLAGS_brownout_clamp_tokens", "FLAGS_breaker_failures",
             "FLAGS_breaker_reset_s", "FLAGS_serving_router"]
    saved = paddle.get_flags(names)
    yield
    paddle.set_flags(saved)
    faults.clear()


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("bucket_cap", 32)
    kw.setdefault("background", False)
    return ServingEngine(model, **kw)


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, (s,)).astype("int64") for s in sizes]


def _prime(eng, n=3, seed=99):
    """Drive enough traffic that the engine's service-time model is
    primed (>= min_samples prefills observed). Sequential — the queue
    never builds, so priming traffic can never itself be shed or
    rejected."""
    for p in _prompts(seed, [5] * n):
        eng.submit(p, max_new_tokens=2)
        eng.run_until_idle()
    assert eng.scheduler.overload.model.primed


def _tighten(eng, min_queue=2, queue_frac=0.25):
    """Drop the live controller's shed watermarks (the flags were read
    at construction; mutating the controller keeps the priming traffic
    unshed and the scenario deterministic)."""
    ov = eng.scheduler.overload
    ov.min_queue = min_queue
    ov.queue_frac = queue_frac


def _ref_tokens(model, prompt, n):
    eng = ContinuousBatchingEngine(model, max_batch=2, block_size=8,
                                   max_seq_len=64, temperature=0.0)
    rid = eng.add_request(prompt, max_new_tokens=n)
    return eng.run_to_completion()[rid]


# -- service-time model (unit) -------------------------------------------


def test_service_time_model_unit():
    m = overload.ServiceTimeModel(alpha=0.5, min_samples=2)
    assert not m.primed
    m.observe_prefill(10, 1000.0)        # 100 us/token
    assert m.prefill_us_per_token == 100.0
    m.observe_prefill(10, 2000.0)        # EWMA toward 200
    assert m.prefill_us_per_token == 150.0
    assert m.primed
    m.observe_decode(50.0)
    wait, ttft = m.predict(queued_tokens=20, queued_requests=2,
                           own_tokens=10)
    # drain = 20 tok * 150 + 2 interleaved steps * 50; TTFT adds own
    # prefill + one step
    assert wait == 20 * 150.0 + 2 * 50.0
    assert ttft == wait + 10 * 150.0 + 50.0


@pytest.mark.parametrize("observe, steady", [
    (lambda m, us: m.observe_decode(us), 36e3),
    # a prefill the scheduler never waits for reads its dispatch alone
    (lambda m, us: m.observe_prefill(128, us), 128.0),
])
def test_one_stalled_dispatch_is_not_a_service_time(observe, steady):
    """A primed estimate takes a sample for at most ``OUTLIER`` times
    itself: one dispatch that a paused host stretched to seconds moves it
    by 1 + alpha * (OUTLIER - 1) and no further; a slowdown that lasts
    still gets there, geometrically."""
    m = overload.ServiceTimeModel()
    read = (lambda: m.decode_step_us) if steady == 36e3 \
        else (lambda: m.prefill_us_per_token * 128)
    observe(m, 10 * steady)              # the first sample is taken whole
    assert read() == pytest.approx(10 * steady)
    for _ in range(40):
        observe(m, steady)
    assert read() == pytest.approx(steady, rel=1e-2)
    observe(m, 4.5e6)                    # one stall of 4.5 s
    assert read() <= steady * 1.01 * (1 + m.alpha * (m.OUTLIER - 1))
    for _ in range(40):
        observe(m, steady)
    lasting = 0
    while read() < 9 * steady:           # ten times slower, and it lasts
        observe(m, 10 * steady)
        lasting += 1
    assert lasting <= 11              # 10 without the clamp


def test_one_stalled_step_sheds_nothing_a_lasting_slowdown_does(
        model, flags_guard):
    """The predicted-wait watermark at a deep queue (64 queued behind a
    36 ms step is 2.3 s of 30): one decode step of 4.5 s used to read as
    0.94 s a step, 60 s of wait, and shed half the queue."""
    eng = _engine(model, max_queue=256)
    _prime(eng)
    ov = eng.scheduler.overload
    for _ in range(10):
        ov.observe_decode(36e3)
    # the block-diffusion cell's: a 36 ms step, prefills never waited for
    ov.model.decode_step_us, ov.model.prefill_us_per_token = 36e3, 1.0
    hs = [eng.submit(p, max_new_tokens=2)
          for p in _prompts(21, [5] * 64)]
    ov.observe_decode(4.5e6)
    # a third is the depth of the queue itself: the 62 that no free slot
    # stands ready for, of 0.75 x 256
    assert ov.pressure(eng.scheduler) == pytest.approx(62 / 192)
    ov.control(eng.scheduler)
    assert all(h.status == RequestStatus.QUEUED for h in hs)
    for _ in range(12):                  # every step that slow: overload
        ov.observe_decode(4.5e6)
    assert ov.pressure(eng.scheduler) >= 1.0
    ov.control(eng.scheduler)
    assert any(h.status == RequestStatus.SHED for h in hs)
    eng.close()


# -- deadline-aware admission --------------------------------------------


def test_unmeetable_deadline_fast_reject(model, flags_guard):
    eng = _engine(model)
    _prime(eng)
    before = metrics.snapshot("serving.admission.")
    with pytest.raises(AdmissionRejected) as ei:
        eng.submit(_prompts(1, [30])[0], max_new_tokens=4,
                   deadline_s=1e-6)
    e = ei.value
    assert e.reason == "deadline"
    assert e.predicted_ttft_s > 0.0
    assert e.retry_after_s is not None and e.retry_after_s > 0.0
    assert e.queue_depth == 0
    # nothing queued, nothing paid: the reject happened before any
    # prefill or block allocation
    assert eng.scheduler.inflight() == 0
    after = metrics.snapshot("serving.admission.")
    assert after["serving.admission.rejected"] == \
        before["serving.admission.rejected"] + 1
    # a generous deadline still admits and completes
    h = eng.submit(_prompts(2, [6])[0], max_new_tokens=3,
                   deadline_s=300.0)
    eng.run_until_idle()
    assert h.status == RequestStatus.DONE
    eng.close()


def test_cold_model_never_rejects(model, flags_guard):
    # unprimed model: even an absurd deadline queues (and later times
    # out at a step boundary) — rejection requires evidence
    eng = _engine(model)
    h = eng.submit(_prompts(3, [6])[0], max_new_tokens=3,
                   deadline_s=1e-6)
    eng.run_until_idle()
    assert h.status == RequestStatus.TIMEOUT
    eng.close()


def test_admission_predict_fault_fails_open(model, flags_guard):
    eng = _engine(model)
    _prime(eng)
    with faults.inject("admission.predict", nth=1):
        h = eng.submit(_prompts(4, [6])[0], max_new_tokens=3,
                       deadline_s=1e-6)  # would reject if predicted
    eng.run_until_idle()
    # fail OPEN: the request was admitted (and expired normally)
    assert h.status == RequestStatus.TIMEOUT
    eng.close()


def test_predicted_ttft_histogram_observed(model, flags_guard):
    before = metrics.snapshot("admission.")[
        "admission.predicted_ttft_us"]["count"]
    eng = _engine(model)
    for p in _prompts(5, [5, 5]):
        eng.submit(p, max_new_tokens=2)
    eng.run_until_idle()
    after = metrics.snapshot("admission.")[
        "admission.predicted_ttft_us"]["count"]
    assert after == before + 2
    eng.close()


# -- priority load shedding ----------------------------------------------


def test_watermark_flags_are_read_at_construction(model, flags_guard):
    paddle.set_flags({"FLAGS_shed_min_queue": 5,
                      "FLAGS_shed_queue_frac": 0.5,
                      "FLAGS_shed_kv_frac": 0.9,
                      "FLAGS_shed_wait_s": 7.0,
                      "FLAGS_admission_optimism": 0.25})
    ov = overload.OverloadController()
    assert (ov.min_queue, ov.queue_frac, ov.kv_frac, ov.wait_s,
            ov.optimism) == (5, 0.5, 0.9, 7.0, 0.25)


def test_priority_shed_order_under_oversubscription(model, flags_guard):
    eng = _engine(model, max_queue=8)
    _prime(eng)
    # tight watermark: shed once more than 2 requests queue
    _tighten(eng)
    high = [eng.submit(p, max_new_tokens=3, priority=overload.HIGH)
            for p in _prompts(6, [5, 6])]
    normal = [eng.submit(p, max_new_tokens=3, priority=overload.NORMAL)
              for p in _prompts(7, [5, 6, 7])]
    low = [eng.submit(p, max_new_tokens=3, priority=overload.LOW)
           for p in _prompts(8, [5, 6, 7])]
    eng.run_until_idle()
    # every HIGH survives; every LOW sheds before any NORMAL order-wise
    assert all(h.status == RequestStatus.DONE for h in high)
    shed_rids = [r.rid for r in eng.scheduler.finished.values()
                 if r.status == RequestStatus.SHED]
    low_rids = [h.rid for h in low]
    normal_rids = [h.rid for h in normal]
    assert shed_rids, "watermark shedding never ran"
    # shed order: all LOW (newest first), then NORMAL (newest first)
    expect = sorted(low_rids, reverse=True)
    if len(shed_rids) > len(low_rids):
        expect += sorted(normal_rids, reverse=True)[
            :len(shed_rids) - len(low_rids)]
    assert shed_rids == expect
    # every shed handle carries the back-off hint (model was primed)
    for h in low:
        if h.status == RequestStatus.SHED:
            assert h.retry_after_s is not None and h.retry_after_s > 0
            assert h.tokens() == []  # never admitted, never decoded
    eng.close()


def test_a_closed_loops_opening_burst_is_not_backlog(model, flags_guard):
    """Twice the slots' requests queued at once on an idle engine (a
    closed loop opening: 256 clients on 128 slots under the flag's
    ``max_queue`` of 256) stand past the queue's watermark, and half of
    them have a free slot waiting: what counts is the rest. The same
    queue behind full slots is shed."""
    eng = _engine(model, max_batch=4, max_queue=8)
    _prime(eng)
    ov = eng.scheduler.overload
    ov.min_queue = 1                       # the watermark: 0.75 x 8 = 6
    hs = [eng.submit(p, max_new_tokens=3)
          for p in _prompts(31, [5, 6, 7, 5, 6, 7, 5, 6])]
    assert ov.pressure(eng.scheduler) == pytest.approx((8 - 4) / 6)
    eng.run_until_idle()
    assert all(h.status == RequestStatus.DONE for h in hs)
    # four running, then eight queued behind them: nothing stands free
    hs = [eng.submit(p, max_new_tokens=8) for p in _prompts(32, [5] * 4)]
    eng.step()
    assert len(eng.scheduler.running) == 4
    late = [eng.submit(p, max_new_tokens=3, priority=overload.LOW)
            for p in _prompts(33, [5] * 8)]
    assert ov.pressure(eng.scheduler) == pytest.approx(8 / 6)
    eng.run_until_idle()
    assert sum(h.status == RequestStatus.SHED for h in late) == 3
    assert all(h.status == RequestStatus.DONE for h in hs)
    eng.close()


def test_shed_counter_and_degrade(model, flags_guard):
    before = metrics.snapshot("serving.shed")["serving.shed"]
    before_deg = metrics.snapshot("resilience.degrade.serving.shed")
    eng = _engine(model, max_queue=8)
    _prime(eng)
    _tighten(eng, min_queue=1, queue_frac=0.125)
    hs = [eng.submit(p, max_new_tokens=2, priority=overload.LOW)
          for p in _prompts(9, [5] * 4)]
    eng.run_until_idle()
    shed = [h for h in hs if h.status == RequestStatus.SHED]
    assert shed
    assert metrics.snapshot("serving.shed")["serving.shed"] \
        == before + len(shed)
    assert metrics.snapshot("resilience.degrade.serving.shed")[
        "resilience.degrade.serving.shed"] == before_deg.get(
        "resilience.degrade.serving.shed", 0) + len(shed)
    eng.close()


def test_survivors_bit_identical_to_uncontended(model, flags_guard):
    prompts = _prompts(10, [5, 7, 6, 9, 5, 8, 7, 6])
    refs = [_ref_tokens(model, p, 4) for p in prompts]
    eng = _engine(model, max_queue=8)
    _prime(eng)
    _tighten(eng)
    hs = [eng.submit(p, max_new_tokens=4,
                     priority=overload.HIGH if i < 3 else overload.LOW)
          for i, p in enumerate(prompts)]
    eng.run_until_idle()
    done = [(h, r) for h, r in zip(hs, refs)
            if h.status == RequestStatus.DONE]
    assert len(done) >= 3  # at least the HIGH class survived
    for h, ref in done:
        assert h.tokens() == list(ref)
    eng.close()


def test_victim_choice_priority_then_newest(model, flags_guard):
    # force preemption with a tiny pool: the LOW-priority request must
    # be the victim even though the HIGH one is newer
    eng = _engine(model, max_batch=2, num_blocks=7, max_seq_len=64)
    low = eng.submit(_prompts(11, [8])[0], max_new_tokens=20,
                     priority=overload.LOW)
    eng.step()  # admit low alone
    high = eng.submit(_prompts(12, [8])[0], max_new_tokens=20,
                      priority=overload.HIGH)
    eng.run_until_idle()
    assert low.status == RequestStatus.DONE
    assert high.status == RequestStatus.DONE
    # the newer HIGH request never got preempted; the older LOW did
    assert high.preempts == 0
    assert low.preempts >= 1
    eng.close()


# -- brownout ladder ------------------------------------------------------


def test_brownout_enter_exit_hysteresis():
    bc = overload.BrownoutController(enter_steps=3, exit_steps=2,
                                     exit_pressure=0.5)
    t0 = metrics.snapshot("serving.brownout.")[
        "serving.brownout.transitions"]
    assert bc.update(2.0) == 0
    assert bc.update(2.0) == 0
    assert bc.update(2.0) == 1          # 3 consecutive over -> stage 1
    assert bc.update(0.8) == 1          # hysteresis band: hold
    assert bc.update(2.0) == 1          # band reset the window
    assert bc.update(2.0) == 1
    assert bc.update(2.0) == 2          # 3 more -> stage 2
    assert bc.update(0.4) == 2          # 1 of 2 exit steps
    assert bc.update(0.8) == 2          # band: exit window resets too
    assert bc.update(0.4) == 2
    assert bc.update(0.4) == 1          # 2 consecutive under -> down
    assert bc.update(0.4) == 1
    assert bc.update(0.4) == 0          # ...and out
    t1 = metrics.snapshot("serving.brownout.")[
        "serving.brownout.transitions"]
    assert t1 == t0 + 4  # 0->1, 1->2, 2->1, 1->0: edges only
    assert metrics.snapshot("serving.brownout.")[
        "serving.brownout.stage"] == 0


def test_brownout_stages_gate_submit(model, flags_guard):
    paddle.set_flags({"FLAGS_brownout_clamp_tokens": 2})
    eng = _engine(model)
    bc = eng.scheduler.overload.brownout
    bc._transition(1, 1.5)  # stage 1: clamp only
    before = metrics.snapshot("serving.brownout.")[
        "serving.brownout.clamped"]
    h = eng.submit(_prompts(13, [5])[0], max_new_tokens=8)
    eng.run_until_idle()
    assert h.status == RequestStatus.DONE
    assert len(h.tokens()) == 2  # clamped from 8
    assert metrics.snapshot("serving.brownout.")[
        "serving.brownout.clamped"] == before + 1
    bc._transition(2, 2.0)  # stage 2: low priorities rejected
    with pytest.raises(AdmissionRejected) as ei:
        eng.submit(_prompts(14, [5])[0], max_new_tokens=2,
                   priority=overload.LOW)
    assert ei.value.reason == "brownout" and ei.value.stage == 2
    h2 = eng.submit(_prompts(14, [5])[0], max_new_tokens=2,
                    priority=overload.NORMAL)  # still admitted
    bc._transition(3, 3.0)  # stage 3: top class only
    with pytest.raises(AdmissionRejected):
        eng.submit(_prompts(15, [5])[0], max_new_tokens=2,
                   priority=overload.NORMAL)
    h3 = eng.submit(_prompts(15, [5])[0], max_new_tokens=2,
                    priority=overload.HIGH)
    eng.run_until_idle()
    assert h2.status == RequestStatus.DONE
    assert h3.status == RequestStatus.DONE
    bc._transition(0, 0.0)
    eng.close()


def test_shed_never_picks_a_preempted_request():
    # a preempted request already streamed tokens to its caller; the
    # SHED contract is "you got nothing, retry safely" — the victim
    # search must skip it (and HIGH), even when it is the lowest
    # priority in the queue
    from paddle_tpu.serving.scheduler import ServingRequest

    preempted = ServingRequest(0, np.arange(5), 4,
                               priority=overload.LOW)
    preempted.generated = [7]            # streamed one token already
    fresh_low = ServingRequest(1, np.arange(5), 4,
                               priority=overload.LOW)
    high = ServingRequest(2, np.arange(5), 4, priority=overload.HIGH)
    ov = overload.OverloadController()
    assert ov._shed_victim([preempted, fresh_low, high]) is fresh_low
    assert ov._shed_victim([preempted, high]) is None


# -- circuit breaker (unit + router wiring) ------------------------------


def test_circuit_breaker_unit():
    br = resilience.CircuitBreaker("unit", failure_threshold=2,
                                   reset_s=0.05)
    assert br.state == br.CLOSED and br.allow()
    assert br.record_failure() is False
    br.record_success()                      # success resets the count
    assert br.record_failure() is False
    assert br.record_failure() is True       # threshold: OPENED here
    assert br.state == br.OPEN
    assert not br.allow()                    # short-circuit
    time.sleep(0.06)
    assert br.state == br.HALF_OPEN
    assert br.allow()                        # the single probe
    assert not br.allow()                    # probe in flight: refused
    assert br.record_success() is True       # probe healthy: CLOSED
    assert br.state == br.CLOSED
    # a failing probe re-opens
    br.record_failure()
    br.record_failure()
    time.sleep(0.06)
    assert br.allow()
    assert br.record_failure() is True       # probe failed: OPEN again
    assert br.state == br.OPEN


def test_router_breaker_open_skip_and_recover(model, flags_guard):
    paddle.set_flags({"FLAGS_breaker_failures": 2,
                      "FLAGS_breaker_reset_s": 0.2})
    e1 = _engine(model)
    e2 = _engine(model)
    router = Router()
    router.add_replica("b1", engine=e1)
    router.add_replica("b2", engine=e2)
    opened0 = metrics.snapshot("router.breaker.").get(
        "router.breaker.opened", 0)
    faults.arm("router.submit.b1", nth=1, count=10 ** 6)
    try:
        for p in _prompts(16, [5, 5]):
            router.submit(p, max_new_tokens=2)  # b1 fails, lands b2
        assert metrics.snapshot("router.breaker.")[
            "router.breaker.opened"] == opened0 + 1
        hits_after_open = faults.hits("router.submit.b1")
        hs = [router.submit(p, max_new_tokens=2)
              for p in _prompts(17, [5, 6, 7])]
        # breaker open: b1 skipped outright — no further submit
        # attempts hammer it, everything lands on b2
        assert faults.hits("router.submit.b1") == hits_after_open
        assert all(h.replica_id == "b2" for h in hs)
        assert metrics.snapshot("router.breaker.")[
            "router.breaker.skipped"] >= 3
    finally:
        faults.disarm("router.submit.b1")
    # recovery: past the reset window one probe goes through, succeeds,
    # and closes the breaker — b1 is routable again
    time.sleep(0.25)
    closed0 = metrics.snapshot("router.breaker.").get(
        "router.breaker.closed", 0)
    probe = router.submit(_prompts(18, [5])[0], max_new_tokens=2)
    assert metrics.snapshot("router.breaker.")[
        "router.breaker.closed"] == closed0 + 1
    for eng in (e1, e2):
        eng.run_until_idle()
    assert probe.status == RequestStatus.DONE
    e1.close()
    e2.close()


def test_breaker_probe_release_unit():
    br = resilience.CircuitBreaker("probe-unit", failure_threshold=1,
                                   reset_s=0.05)
    br.record_failure()                      # open
    time.sleep(0.06)
    assert br.allow()                        # probe consumed
    br.release_probe()                       # policy refusal: no verdict
    assert br.state == br.HALF_OPEN
    assert br.allow()                        # next probe immediately
    assert br.record_success() is True       # ...and it can still close
    assert br.state == br.CLOSED


def test_breaker_probe_not_wedged_by_policy_rejection(model,
                                                      flags_guard):
    # the half-open probe hitting QueueFullError (likely during the
    # very incident that opened the breaker) must RELEASE the probe
    # slot — recovery can never wedge behind a verdict-less probe.
    # Single replica: every sweep MUST consult its breaker.
    paddle.set_flags({"FLAGS_breaker_failures": 1,
                      "FLAGS_breaker_reset_s": 0.1})
    busy = _engine(model, max_queue=1)
    router = Router()
    router.add_replica("w1", engine=busy)
    with faults.inject("router.submit.w1", nth=1, count=10):
        with pytest.raises(NoReplicaAvailable):
            router.submit(_prompts(27, [5])[0], max_new_tokens=2)
    assert router._breakers["w1"].state == \
        resilience.CircuitBreaker.OPEN
    busy.submit(_prompts(27, [6])[0], max_new_tokens=2)  # queue full
    time.sleep(0.12)
    # the probe is consumed and answered with a QueueFullError policy
    # refusal: released, not wedged (pre-fix this left _probe_inflight
    # True forever and every later sweep read breaker-open)
    with pytest.raises(NoReplicaAvailable) as ei:
        router.submit(_prompts(28, [5])[0], max_new_tokens=2)
    assert ei.value.reasons["w1"] == "QueueFullError"
    assert router._breakers["w1"].state == \
        resilience.CircuitBreaker.HALF_OPEN
    busy.run_until_idle()                    # drain the busy queue
    probe = router.submit(_prompts(28, [6])[0], max_new_tokens=2)
    assert router._breakers["w1"].state == \
        resilience.CircuitBreaker.CLOSED     # next probe closed it
    busy.run_until_idle()
    assert probe.status == RequestStatus.DONE
    busy.close()


def test_breaker_ignores_policy_rejections(model, flags_guard):
    # QueueFullError/NotReadyError/AdmissionRejected come from a
    # HEALTHY replica doing its job — they must never open its breaker
    # (which would blackhole traffic the replica still accepts)
    paddle.set_flags({"FLAGS_breaker_failures": 1})
    full = _engine(model, max_queue=1)
    healthy = _engine(model)
    full.submit(_prompts(25, [5])[0], max_new_tokens=2)  # queue full
    router = Router()
    router.add_replica("p1", engine=full)
    router.add_replica("p2", engine=healthy)
    opened0 = metrics.snapshot("router.breaker.").get(
        "router.breaker.opened", 0)
    hs = [router.submit(p, max_new_tokens=2)
          for p in _prompts(26, [5, 6, 7])]
    # p1 refused each sweep with QueueFullError yet its breaker stayed
    # CLOSED; traffic simply moved on to the healthy replica
    assert metrics.snapshot("router.breaker.").get(
        "router.breaker.opened", 0) == opened0
    assert all(h.replica_id == "p2" for h in hs)
    for eng in (full, healthy):
        eng.run_until_idle()
    full.close()
    healthy.close()


# -- structured rejections ------------------------------------------------


def test_queue_full_error_structured_fields(model, flags_guard):
    eng = _engine(model, max_queue=1)
    _prime(eng)
    eng.submit(_prompts(19, [5])[0], max_new_tokens=2)  # fills the queue
    with pytest.raises(QueueFullError) as ei:
        eng.submit(_prompts(19, [6])[0], max_new_tokens=2)
    e = ei.value
    assert e.queue_depth == 1 and e.max_queue == 1
    assert e.retry_after_s is not None and e.retry_after_s > 0
    eng.run_until_idle()
    eng.close()


def test_no_replica_available_aggregates_reasons(model, flags_guard):
    warming = _engine(model, ready=False)          # WARMING: not routable
    full = _engine(model, max_queue=1)
    _prime(full)
    full.submit(_prompts(20, [5])[0], max_new_tokens=2)  # fill the queue
    router = Router()
    router.add_replica("w1", engine=warming)
    router.add_replica("f1", engine=full)
    with pytest.raises(NoReplicaAvailable) as ei:
        router.submit(_prompts(20, [6])[0], max_new_tokens=2)
    e = ei.value
    assert e.reasons["w1"] == "NotReady(WARMING)"
    assert e.reasons["f1"] == "QueueFullError"
    assert e.retry_after_s is not None and e.retry_after_s > 0
    assert "w1" in str(e) and "QueueFullError" in str(e)
    full.run_until_idle()
    full.close()
    warming.close()


# -- shed.rate alert rule -------------------------------------------------


def test_shed_rate_alert_fires_once_per_episode():
    shed = metrics.counter("serving.shed")
    mgr = alerts_mod.AlertManager(rules=[alerts_mod.ShedRateRule()])
    mgr.evaluate()                       # priming window
    shed.inc(3)
    fired = mgr.evaluate()
    assert [i["rule"] for i in fired] == ["shed.rate"]
    assert fired[0]["severity"] == "page"
    shed.inc(2)
    assert mgr.evaluate() == []          # still active: no refire
    assert [i["rule"] for i in mgr.active()] == ["shed.rate"]
    assert mgr.evaluate() == []          # zero sheds: resolves
    assert mgr.active() == []
    assert [i["rule"] for i in mgr.history()] == ["shed.rate"]


# -- flags-off revert -----------------------------------------------------


def test_flags_off_reverts_byte_for_byte(model, flags_guard):
    paddle.set_flags({"FLAGS_serving_admission": False,
                      "FLAGS_serving_brownout": False,
                      "FLAGS_router_breaker": False,
                      # watermarks that WOULD shed if the plane ran
                      "FLAGS_shed_min_queue": 1,
                      "FLAGS_shed_queue_frac": 0.01})
    prompts = _prompts(21, [5, 7, 6, 9])
    refs = [_ref_tokens(model, p, 3) for p in prompts]
    before = {pre: metrics.snapshot(pre) for pre in
              ("serving.shed", "serving.admission.",
               "serving.brownout.", "admission.", "router.breaker.")}
    eng = _engine(model, max_queue=8)
    assert eng.scheduler.overload is overload.NULL
    router = Router()
    router.add_replica("r1", engine=eng)
    assert router._breaker_armed is False
    # priority + tiny deadline are accepted and INERT: no rejection,
    # no shedding, statuses and outputs exactly the pre-overload ones
    hs = [eng.submit(p, max_new_tokens=3, priority=overload.LOW)
          for p in prompts]
    eng.run_until_idle()
    assert [h.status for h in hs] == [RequestStatus.DONE] * 4
    for h, ref in zip(hs, refs):
        assert h.tokens() == list(ref)
        assert h.retry_after_s is None
    for pre, snap in before.items():
        assert metrics.snapshot(pre) == snap, pre
    eng.close()


def test_flag_routing_reads_at_construction(model, flags_guard):
    # ctor kwargs override the flags, the accounting convention
    eng = _engine(model, admission=False, brownout=False)
    assert eng.scheduler.overload is overload.NULL
    eng.close()
    eng = _engine(model, admission=True, brownout=False)
    assert eng.scheduler.overload.shedding is True
    assert eng.scheduler.overload.brownout is None
    eng.close()
    eng = _engine(model, admission=False, brownout=True)
    assert eng.scheduler.overload.shedding is False
    assert eng.scheduler.overload.brownout is not None
    eng.close()
