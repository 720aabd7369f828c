"""The plain decode loop runs one step ahead (docs/SERVING.md "The
decode loop runs one step ahead"): step K+1 is dispatched with step K's
tokens still on the device, and the host reads and emits K's while K+1
runs. Pinned here, on the CPU at the tiny width: every request gets the
tokens, the status and the ``on_token`` order of the same run read in
order (``Scheduler.land()`` after every step), whatever changes who is
running; the dispatch really precedes the read; and the step time fed to
overload control is the in-order run's.
"""

import collections
import contextlib

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import Llama, LlamaConfig
from paddle_tpu.profiler import metrics
from paddle_tpu.serving import scheduler as scheduler_mod
from paddle_tpu.serving.scheduler import RequestStatus, Scheduler


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 255, (s,)).astype("int64") for s in sizes]


class _Deadline:
    """A deadline the script expires."""

    def __init__(self):
        self.over = False

    def expired(self):
        return self.over

    def remaining(self):
        return 0.0 if self.over else 1e9


def _counters():
    snap = metrics.snapshot("serving.")
    return {k: snap[k] for k in ("serving.decode.ahead",
                                 "serving.decode.in_order",
                                 "serving.preempt", "serving.steps")}


def _run(model, script, *, ahead, seed=None, **kw):
    """Drive a Scheduler through ``script``: {step index: [event]}, an
    event ``("submit", prompt, n)``, ``("cancel", i)`` or ``("expire",
    i)`` on the i-th submitted request, applied before that step. In
    order (``ahead=False``) every step is followed by ``land()``.
    Returns the scheduler, the requests in submission order, the tokens
    ``on_token`` saw for each and the counters' movement."""
    if seed is not None:
        paddle.seed(seed)
    kw.setdefault("temperature", 0.0)
    sched = Scheduler(model, **{"max_batch": 4, "block_size": 8,
                                "max_seq_len": 64, **kw})
    reqs, seen, deadlines = [], [], []
    before = _counters()
    step = 0
    while step <= max(script) or sched.has_work:
        for ev in script.get(step, ()):
            if ev[0] == "submit":
                seen.append([])
                deadlines.append(_Deadline())
                reqs.append(sched.submit(
                    ev[1], max_new_tokens=ev[2], deadline=deadlines[-1],
                    on_token=lambda r, t, sink=seen[-1]: sink.append(t)))
            elif ev[0] == "cancel":
                sched.cancel(reqs[ev[1]])
            else:
                deadlines[ev[1]].over = True
        sched.step()
        if not ahead:
            sched.land()
        step += 1
        assert step < 500
    after = _counters()
    return sched, reqs, seen, {k: after[k] - before[k] for k in after}


def _same(a, b):
    (_, reqs_a, seen_a, _), (_, reqs_b, seen_b, _) = a, b
    assert [r.status for r in reqs_a] == [r.status for r in reqs_b]
    assert [r.generated for r in reqs_a] == [r.generated for r in reqs_b]
    assert seen_a == seen_b
    assert [list(r.generated) for r in reqs_a] == seen_a


_P = _prompts(31, [5, 9, 12, 6, 14])

# name -> (script, engine keywords, statuses wanted)
_CASES = {
    "admitted-mid-stream": (
        {0: [("submit", _P[0], 10)], 2: [("submit", _P[1], 6)],
         3: [("submit", _P[2], 7), ("submit", _P[3], 3)]}, {},
        ["DONE"] * 4),
    "finish-by-count": (
        {0: [("submit", _P[0], 1), ("submit", _P[1], 2),
             ("submit", _P[2], 5), ("submit", _P[3], 9)],
         4: [("submit", _P[4], 2)]}, {}, ["DONE"] * 5),
    "oversubscribed-slots": (
        {0: [("submit", p, 4 + i) for i, p in enumerate(_P)]},
        {"max_batch": 2}, ["DONE"] * 5),
    "cancel-while-in-flight": (
        {0: [("submit", _P[0], 12), ("submit", _P[1], 12)],
         4: [("cancel", 0)]}, {}, ["CANCELLED", "DONE"]),
    "deadline-while-in-flight": (
        {0: [("submit", _P[0], 12), ("submit", _P[1], 12)],
         5: [("expire", 1)]}, {}, ["DONE", "TIMEOUT"]),
    "cancel-on-its-last-token": (
        {0: [("submit", _P[0], 4)], 3: [("cancel", 0)]}, {}, ["DONE"]),
    "prefix-cache-hit": (
        {0: [("submit", np.concatenate([_P[2], _P[0]]), 8)],
         3: [("submit", np.concatenate([_P[2], _P[1]]), 8)],
         5: [("submit", np.concatenate([_P[2], _P[0]]), 5)]},
        {"prefix_cache": True}, ["DONE"] * 3),
    "int8-pools": (
        {0: [("submit", _P[0], 8)], 2: [("submit", _P[1], 6)]},
        {"kv_cache_dtype": "int8"}, ["DONE"] * 2),
}


@pytest.mark.parametrize("name", list(_CASES))
def test_one_step_ahead_serves_what_in_order_serves(model, name):
    script, kw, statuses = _CASES[name]
    ahead = _run(model, script, ahead=True, **kw)
    in_order = _run(model, script, ahead=False, **kw)
    _same(ahead, in_order)
    assert [r.status for r in ahead[1]] == statuses
    assert ahead[3]["serving.decode.ahead"] > 0
    assert in_order[3]["serving.decode.ahead"] == 0
    sched = ahead[0]
    assert not sched.has_work and sched.inflight() == 0
    assert sched.cache.num_free_blocks() == sched.cache.num_blocks - 1


def test_a_fresh_slot_is_merged_on_the_device(model, monkeypatch):
    """A slot admitted while a step is in flight takes its prefill's
    token through ``merge_tokens``; the step's own array is never read
    for it (the span-order test below holds the order)."""
    calls = []
    real = scheduler_mod.merge_tokens

    def merge(prev, host, fresh):
        calls.append(np.flatnonzero(fresh).tolist())
        assert not isinstance(prev, np.ndarray)
        return real(prev, host, fresh)

    script = _CASES["admitted-mid-stream"][0]
    _run(model, script, ahead=True)  # warm: the merge's program too
    monkeypatch.setattr(scheduler_mod, "merge_tokens", merge)
    _run(model, script, ahead=True)
    assert calls == [[1], [2, 3]]
    del calls[:]
    _run(model, script, ahead=False)
    assert calls == []


def test_temperature_under_a_fixed_seed(model):
    """The sampling keys are drawn on the host at dispatch, in the same
    order: seeded outputs are unchanged (no request waits for a slot
    here: a freed slot is refilled one step later, which moves a
    prefill's key)."""
    script = {0: [("submit", _P[0], 9)], 2: [("submit", _P[1], 7)]}
    ahead = _run(model, script, ahead=True, seed=77, temperature=0.8)
    in_order = _run(model, script, ahead=False, seed=77, temperature=0.8)
    _same(ahead, in_order)
    greedy = _run(model, script, ahead=True)
    assert ahead[2] != greedy[2]


def test_an_eos_that_fires(model):
    """With an ``eos_token_id`` a slot that emitted EOS in step K has
    run in step K+1: that token is dropped, and the write lands in a
    block the slot still owned, past what the prefix cache registered."""
    script = {0: [("submit", _P[2], 12), ("submit", _P[1], 12)],
              2: [("submit", _P[0], 12)]}
    free = _run(model, script, ahead=True, prefix_cache=True)
    eos = free[1][0].generated[4]
    runs = [_run(model, script, ahead=a, prefix_cache=True,
                 eos_token_id=eos) for a in (True, False)]
    _same(*runs)
    stopped = [r for r in runs[0][1] if r.generated[-1] == eos]
    assert stopped and all(r.generated.index(eos) == len(r.generated) - 1
                           for r in stopped)
    assert len(runs[0][1][0].generated) == 5
    assert all(r.status == RequestStatus.DONE for r in runs[0][1])
    # what the cache holds of the stopped request's prompt
    rows = []
    for sched, *_ in runs:
        plan = sched.cache.plan_prefix(_P[2])
        assert plan.covered_tokens == len(_P[2])
        blocks = plan.matched_blocks + [plan.partial_block]
        pool = np.array(sched.cache.k_pools[0], np.float32)
        flat = pool[blocks].reshape((-1,) + pool.shape[2:])
        rows.append(flat[:plan.covered_tokens])
    np.testing.assert_array_equal(*rows)
    assert not runs[0][0].has_work


def test_preemption_pin_holds_one_step_ahead(model):
    """Pool exhaustion reads the step in flight before it preempts: the
    requeued prompt + generated holds the token that was in flight, and
    the greedy outputs are those of an uncontended run."""
    p1, p2 = _prompts(5, [8, 8])
    script = {0: [("submit", p1, 12), ("submit", p2, 12)]}
    kw = dict(max_batch=2, block_size=4, max_seq_len=32, num_blocks=8)
    ahead = _run(model, script, ahead=True, **kw)
    in_order = _run(model, script, ahead=False, **kw)
    _same(ahead, in_order)
    assert ahead[3]["serving.preempt"] > 0
    alone = [_run(model, {0: [("submit", p, 12)]}, ahead=False, **kw)
             for p in (p1, p2)]
    assert [r.generated for r in ahead[1]] \
        == [a[1][0].generated for a in alone]
    assert all(r.status == RequestStatus.DONE for r in ahead[1])


def test_speculation_stays_in_order(model):
    """Drafts are proposed from tokens on the host: with ``spec`` armed
    no dispatch runs ahead, whichever program a step ends up running."""
    rep = np.tile(_P[0], 3)  # something for prompt lookup to find
    script = {0: [("submit", rep, 10)], 2: [("submit", _P[1], 8)]}
    spec = _run(model, script, ahead=True, spec=True)
    plain = _run(model, script, ahead=True)
    assert spec[3]["serving.decode.ahead"] == 0
    assert spec[3]["serving.decode.in_order"] > 0
    assert [r.generated for r in spec[1]] \
        == [r.generated for r in plain[1]]


@contextlib.contextmanager
def _recorded_phases(monkeypatch):
    """The scheduler's phases, by name, in the order they opened."""
    opened = []
    real = scheduler_mod._phase

    def phase(name, **attrs):
        opened.append(name)
        return real(name, **attrs)

    monkeypatch.setattr(scheduler_mod, "_phase", phase)
    yield opened


def test_the_next_dispatch_opens_before_the_read_back(model, monkeypatch):
    """Over N warm steps: ``serving.decode.dispatch`` of step K+1 opens
    before ``serving.decode.readback`` of step K, N - 1 dispatches ran
    ahead and the first (after idle) in order."""
    n = 9
    _run(model, {0: [("submit", _P[0], 3)]}, ahead=True)  # warm
    with _recorded_phases(monkeypatch) as opened:
        _, reqs, _, moved = _run(
            model, {0: [("submit", _P[0], n + 1)]}, ahead=True)
    assert len(reqs[0].generated) == n + 1
    order = [p.rsplit(".", 1)[1] for p in opened
             if p in ("serving.decode.dispatch", "serving.decode.readback")]
    assert order == ["dispatch"] + ["dispatch", "readback"] * (n - 1) \
        + ["readback"]
    assert moved["serving.decode.ahead"] == n - 1
    assert moved["serving.decode.in_order"] == 1
    # in order, for contrast: each step is read before the next
    with _recorded_phases(monkeypatch) as opened:
        _, _, _, moved = _run(
            model, {0: [("submit", _P[0], n + 1)]}, ahead=False)
    order = [p.rsplit(".", 1)[1] for p in opened
             if p in ("serving.decode.dispatch", "serving.decode.readback")]
    assert order == ["dispatch", "readback"] * n
    assert (moved["serving.decode.ahead"],
            moved["serving.decode.in_order"]) == (0, n)


class _SimulatedChip:
    """A clock the test owns and a device behind it: a decode dispatch
    costs the host ``DISPATCH`` and queues ``STEP`` of device time behind
    what is queued; a read-back returns when its step is done; the emit
    loop costs ``EMIT``. Stands in for ``time`` and ``_phase`` of the
    scheduler module."""

    STEP, DISPATCH, EMIT = 10_000_000, 300_000, 800_000  # ns

    def __init__(self):
        self.now = 1_000_000_000
        self.busy_until = 0
        self.ready = collections.deque()

    def perf_counter_ns(self):
        return self.now

    def monotonic(self):
        return self.now / 1e9

    @contextlib.contextmanager
    def phase(self, name, **attrs):
        yield
        if name == "serving.decode.dispatch":
            self.now += self.DISPATCH
            self.busy_until = max(self.now, self.busy_until) + self.STEP
            self.ready.append(self.busy_until)
        elif name == "serving.decode.readback":
            self.now = max(self.now, self.ready.popleft())
        elif name == "serving.decode.emit":
            self.now += self.EMIT


def _fed_to_overload(model, monkeypatch, script, ahead):
    chip = _SimulatedChip()
    monkeypatch.setattr(scheduler_mod, "time", chip)
    monkeypatch.setattr(scheduler_mod, "_phase", chip.phase)
    fed = []
    monkeypatch.setattr(
        scheduler_mod._overload.OverloadController, "observe_decode",
        lambda self, us: fed.append(us))
    *_, moved = _run(model, script, ahead=ahead, admission=True)
    return fed, moved


def test_overload_control_is_fed_the_step_not_the_hosts_share(
        model, monkeypatch):
    """One step ahead, the wall time of dispatch + read-back is the
    host's own cost: what ``observe_decode`` gets is the time between
    two arrivals, which under a simulated clock is the in-order run's
    number to within the dispatch's cost."""
    chip = _SimulatedChip
    mixed = {0: [("submit", _P[0], 12)], 4: [("submit", _P[1], 4)]}
    _run(model, mixed, ahead=True)  # warm: a built program is read at once
    script = {0: [("submit", _P[0], 12)]}
    ahead, moved = _fed_to_overload(model, monkeypatch, script, True)
    in_order, _ = _fed_to_overload(model, monkeypatch, script, False)
    assert moved["serving.decode.ahead"] == 10
    assert len(ahead) == len(in_order) == 11
    assert all(us == (chip.STEP + chip.DISPATCH) / 1000 for us in in_order)
    for a, b in zip(ahead, in_order):
        assert chip.STEP / 1000 <= a <= b
    # a step a prefill was read back behind cannot be timed: it feeds
    # nothing, and the others still read a whole step
    ahead, moved = _fed_to_overload(model, monkeypatch, mixed, True)
    dispatches = moved["serving.decode.ahead"] \
        + moved["serving.decode.in_order"]
    assert len(ahead) == dispatches - 1
    assert all(chip.STEP / 1000 <= us
               <= (chip.STEP + chip.DISPATCH + chip.EMIT) / 1000
               for us in ahead)


def test_a_step_in_flight_counts_as_work(model):
    sched = Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                      temperature=0.0)
    # warm: a dispatch that builds its program is read at once
    sched.submit(_P[0], max_new_tokens=3)
    sched.run_to_completion()
    req = sched.submit(_P[0], max_new_tokens=3)
    sched.step()  # prefill + the first decode step, unread
    assert len(req.generated) == 1 and sched.has_work
    assert sched.inflight() == 1
    sched.step()
    assert len(req.generated) == 2
    assert sched.run_to_completion()[req.rid] == req.generated
    assert len(req.generated) == 3 and not sched.has_work
    # the engine dying delivers what the last step made, then ERROR
    req = sched.submit(_P[1], max_new_tokens=5)
    sched.step()
    sched.fail_all()
    assert req.status == RequestStatus.ERROR and len(req.generated) == 2
    assert not sched.has_work
