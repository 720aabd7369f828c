"""The block step runs one step ahead (docs/SERVING.md "Block-diffusion
decoding"): the unmasking rule runs inside ``jit_sdar_block_step``, the
open blocks stay on the device as the next step's input, and the host
reads a step, and emits what it committed, while the next one runs.
Pinned here, on the CPU at the tiny width:

- every request gets the tokens, the status and the ``on_token`` order of
  the same run read in order (``Scheduler.land()`` after every step) and
  of the plain reference's ``generate``, whatever changes who is running;
- the rule on the device is ``reference.pick_unmasked`` under the plan of
  ``reference.unmask_counts``, ties and an arg-max equal to the mask id
  included;
- the observer's records one step ahead are the in-order ones;
- the dispatch really precedes the read, and the counters add up the same;
- ``warmup()`` leaves nothing to compile.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import SDAR, SDARConfig
from paddle_tpu.models.sdar import low_confidence_static
from paddle_tpu.profiler import metrics
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import scheduler as scheduler_mod
from paddle_tpu.serving.scheduler import RequestStatus, Scheduler

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.reference import sdar_moe_blockdiff as R  # noqa: E402
from tests.framework.test_decode_ahead import (  # noqa: E402
    _Deadline, _recorded_phases, _same)

PAD = 64  # one padded length, so that the reference compiles once
_COUNTED = ("serving.decode.ahead", "serving.decode.in_order",
            "serving.preempt", "serving.decoded_tokens",
            "serving.blockdiff.denoise_forwards",
            "serving.blockdiff.commit_forwards",
            "serving.blockdiff.blocks_committed",
            "serving.blockdiff.tokens_unmasked", "serving.moe.rows",
            "serving.moe.experts_hit", "serving.moe.max_rows")


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = SDAR(SDARConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def generate(model):
    cfg = model.config
    fields = {"num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
              "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
              "rms_norm_eps": cfg.rms_norm_eps,
              "top_k": cfg.num_experts_per_tok,
              "norm_topk_prob": cfg.norm_topk_prob,
              "block_length": cfg.block_length}
    weights = R.weights_of(model)

    def generate(prompt, n):
        return R.generate(weights, fields, prompt, n,
                          denoise_steps=cfg.denoise_steps,
                          mask_token_id=cfg.mask_token_id, pad_to=PAD)
    return generate


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, size=n) for n in sizes]


def _counters():
    snap = metrics.snapshot("serving.")
    return {k: snap[k] for k in _COUNTED}


def _run(model, script, *, ahead, **kw):
    """Drive a Scheduler through ``script``: {step index: [event]}, an
    event ``("submit", prompt, n)``, ``("cancel", i)`` or ``("expire",
    i)`` on the i-th submitted request, or ``("observe", sink)`` (every
    slot-forward launched from now on is recorded there), applied before
    that step. In order (``ahead=False``) every step is followed by
    ``land()``. Returns the scheduler, the requests in submission order,
    the tokens ``on_token`` saw for each and the counters' movement."""
    sched = Scheduler(model, **{"max_batch": 4, "block_size": 8,
                                "max_seq_len": 64, "dtype": jnp.float32,
                                **kw})
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
            elif ev[0] == "expire":
                deadlines[ev[1]].over = True
            else:
                sched.block_observer = ev[1].append
        sched.step()
        if not ahead:
            sched.land()
        step += 1
        assert step < 500
    after = _counters()
    return sched, reqs, seen, {k: after[k] - before[k] for k in after}


# prompts that leave 0, 1, 2 and 3 tokens over past their last whole block
_P = _prompts(41, [8, 13, 6, 11, 16, 9])

# name -> (script, engine keywords, statuses wanted)
_CASES = {
    "admitted-mid-stream-with-every-left-over": (
        {0: [("submit", _P[0], 10)], 2: [("submit", _P[1], 6)],
         3: [("submit", _P[2], 9), ("submit", _P[3], 5)],
         7: [("submit", _P[4], 4), ("submit", _P[5], 12)]}, {},
        ["DONE"] * 6),
    "finish-by-count-inside-a-block": (
        {0: [("submit", _P[0], 1), ("submit", _P[1], 2),
             ("submit", _P[2], 7), ("submit", _P[3], 9)],
         4: [("submit", _P[4], 3)]}, {}, ["DONE"] * 5),
    "oversubscribed-slots": (
        {0: [("submit", p, 5 + i) for i, p in enumerate(_P)]},
        {"max_batch": 2}, ["DONE"] * 6),
    "cancel-while-in-flight": (
        {0: [("submit", _P[0], 16), ("submit", _P[1], 16)],
         5: [("cancel", 0)]}, {}, ["CANCELLED", "DONE"]),
    "deadline-while-in-flight": (
        {0: [("submit", _P[0], 16), ("submit", _P[3], 16)],
         6: [("expire", 1)]}, {}, ["DONE", "TIMEOUT"]),
    "cancel-as-its-last-block-commits": (
        {0: [("submit", _P[0], 4)], 3: [("cancel", 0)]}, {}, ["DONE"]),
    "prefix-cache-hit": (
        {0: [("submit", np.concatenate([_P[4], _P[0]]), 8)],
         4: [("submit", np.concatenate([_P[4], _P[1]]), 8)],
         6: [("submit", np.concatenate([_P[4], _P[0]]), 6)]},
        {"prefix_cache": True}, ["DONE"] * 3),
    "preempted-between-two-denoising-forwards": (
        {0: [("submit", p, 12) for p in _prompts(5, [8, 7])]},
        {"max_batch": 2, "block_size": 4, "max_seq_len": 32,
         "num_blocks": 7}, ["DONE"] * 2),
}


@pytest.mark.parametrize("name", list(_CASES))
def test_one_step_ahead_serves_what_in_order_and_the_reference_do(
        model, generate, name):
    script, kw, statuses = _CASES[name]
    ahead = _run(model, script, ahead=True, **kw)
    in_order = _run(model, script, ahead=False, **kw)
    _same(ahead, in_order)
    assert [r.status for r in ahead[1]] == statuses
    assert ahead[3]["serving.decode.ahead"] > 0
    assert in_order[3]["serving.decode.ahead"] == 0
    if name.startswith("preempted"):
        assert ahead[3]["serving.preempt"] > 0
    submits = [ev for evs in script.values() for ev in evs
               if ev[0] == "submit"]
    for req, (_, prompt, n) in zip(ahead[1], submits):
        want = generate(prompt, n)
        if req.status == RequestStatus.DONE:
            assert req.generated == want
        else:  # what it was handed before it was withdrawn: whole blocks
            assert req.generated == want[:len(req.generated)]
    sched = ahead[0]
    assert not sched.has_work and sched.inflight() == 0
    assert sched.cache.num_free_blocks() == sched.cache.num_blocks - 1
    # apart from who ran ahead, both runs counted the same work (a run
    # with an EOS would not: a stopped slot runs one step for nothing).
    # Which experts a step hits hangs on who shares it, and a slot freed
    # a step later admits its successor a step later
    for k in _COUNTED[2:-2]:
        assert ahead[3][k] == in_order[3][k], k


def test_an_eos_inside_a_committed_block_with_a_step_in_flight(
        model, generate):
    """A slot whose commit held EOS has run in the step dispatched
    meanwhile: that step's result for it is dropped, the tokens after the
    EOS in its block are not handed out, and its slot's next tenant opens
    its own block."""
    script = {0: [("submit", _P[0], 16), ("submit", _P[1], 16)],
              2: [("submit", _P[2], 16)], 9: [("submit", _P[3], 6)]}
    free = _run(model, script, ahead=True, max_batch=3)
    eos = free[1][0].generated[5]  # the second position of a block
    runs = [_run(model, script, ahead=a, max_batch=3, eos_token_id=eos)
            for a in (True, False)]
    _same(*runs)
    stopped = [r for r in runs[0][1] if r.generated[-1] == eos]
    assert stopped and all(r.generated.index(eos) == len(r.generated) - 1
                           for r in stopped)
    first = runs[0][1][0]
    assert len(first.generated) <= 6 and first.generated[-1] == eos
    for req, (_, prompt, n) in zip(runs[0][1], (
            ev for evs in script.values() for ev in evs)):
        want = generate(prompt, n)
        if eos in want:
            want = want[:want.index(eos) + 1]
        assert req.generated == want
        assert req.status == RequestStatus.DONE
    assert not runs[0][0].has_work
    assert runs[0][3]["serving.decode.ahead"] > 0


def test_a_fresh_slots_open_block_is_merged_on_the_device(
        model, monkeypatch):
    """A slot admitted while a step is in flight brings its open block
    (the prompt's left-over tokens, the rest masked) through
    ``merge_tokens``, only in a step that has such a slot; the blocks of
    the others are the step's own output, never read for the launch."""
    calls = []
    real = scheduler_mod.merge_tokens

    def merge(prev, host, fresh):
        calls.append((np.flatnonzero(fresh).tolist(),
                      np.asarray(host)[np.flatnonzero(fresh)].tolist()))
        assert not isinstance(prev, np.ndarray)
        return real(prev, host, fresh)

    script = {0: [("submit", _P[0], 10)], 2: [("submit", _P[1], 6)],
              3: [("submit", _P[2], 9), ("submit", _P[3], 5)]}
    _run(model, script, ahead=True)  # warm: the merge's program too
    monkeypatch.setattr(scheduler_mod, "merge_tokens", merge)
    _run(model, script, ahead=True)
    mask = model.config.mask_token_id
    assert [slots for slots, _ in calls] == [[1], [2, 3]]
    # ids, mask, opened with, forwards done: 13 = 3 x 4 + 1 given
    assert calls[0][1] == [[int(_P[1][12]), mask, mask, mask,
                            0, 1, 1, 1, 3, 0]]
    assert calls[1][1][0] == [int(_P[2][4]), int(_P[2][5]), mask, mask,
                              0, 0, 1, 1, 2, 0]
    assert calls[1][1][1][4:] == [0, 0, 0, 1, 1, 0]
    del calls[:]
    _run(model, script, ahead=False)
    assert calls == []


# -- the rule on the device ---------------------------------------------------

@pytest.mark.parametrize("steps", [1, 2, 3])
@pytest.mark.parametrize("opened", [1, 2, 3, 4])
def test_the_device_rule_is_the_references(opened, steps):
    """Blocks that opened with ``opened`` masked positions, through their
    denoising forwards and their commit: on probabilities drawn from four
    values (ties everywhere) and tokens that are the mask id a third of
    the time, every forward unmasks what ``reference.pick_unmasked``
    picks of ``reference.unmask_counts``' plan, fills those positions in
    and no other, and the commit opens the next block all masked. A slot
    that is not active keeps its state."""
    width, batch, mask_id = 4, 48, 255
    rng = np.random.default_rng(100 * opened + steps)
    given = width - opened
    ids = np.full((batch, width), mask_id, np.int32)
    ids[:, :given] = rng.integers(0, 250, (batch, given))
    masked = np.tile(np.arange(width) >= given, (batch, 1))
    state = (ids, masked, np.full((batch,), opened, np.int32),
             np.zeros((batch,), np.int32))
    active = np.arange(batch) % 8 != 7
    plan = R.unmask_counts(opened, steps)
    assert len(plan) == min(opened, steps)
    for n in plan + [None]:
        probs = rng.choice(np.asarray([0.1, 0.25, 0.25 + 2 ** -20, 0.7],
                                      np.float32), (batch, width))
        toks = np.where(rng.random((batch, width)) < 0.33, mask_id,
                        rng.integers(0, 250, (batch, width))) \
            .astype(np.int32)
        after, picked = low_confidence_static(
            *(jnp.asarray(a) for a in state), jnp.asarray(toks),
            jnp.asarray(probs), jnp.asarray(active), steps, mask_id)
        after = tuple(np.asarray(a) for a in after)
        picked = np.asarray(picked)
        for b in range(batch):
            before = tuple(a[b] for a in state)
            if not active[b]:
                assert not picked[b].any()
                for was, now in zip(before, after):
                    assert np.array_equal(was, now[b])
            elif n is None:  # the commit: nothing masked was left
                assert not before[1].any() and not picked[b].any()
                assert (after[0][b] == mask_id).all() and after[1][b].all()
                assert (after[2][b], after[3][b]) == (width, 0)
            else:
                want = R.pick_unmasked(probs[b], before[1], n)
                assert want.sum() == n
                assert np.array_equal(picked[b], want)
                assert np.array_equal(
                    after[0][b], np.where(want, toks[b], before[0]))
                assert np.array_equal(after[1][b], before[1] & ~want)
                assert (after[2][b], after[3][b]) \
                    == (opened, before[3] + 1)
        state = after
    assert state[1][active].all()  # the next block, opened all masked


# -- the observer rides the flight --------------------------------------------

def test_the_observers_records_one_step_ahead_are_the_in_order_ones(model):
    """The observer is set mid-stream, with a step in flight: both runs
    record the forwards launched from then on, key for key; ``ids`` and
    ``masked`` are what the forward was fed, as the program returns
    them, and ``seq_len`` the committed length it ran at."""
    runs = []
    for ahead in (True, False):
        sink = []
        script = {0: [("submit", _P[0], 10), ("submit", _P[3], 8)],
                  3: [("observe", sink), ("submit", _P[1], 7)]}
        run = _run(model, script, ahead=ahead)
        runs.append((run, sink))
    (ahead, mine), (in_order, theirs) = runs
    _same(ahead, in_order)
    assert len(mine) == len(theirs) > 12
    assert ahead[3]["serving.decode.ahead"] > 0
    keys = {"rid", "step", "seq_len", "ids", "masked", "commit", "tokens",
            "logits", "probs", "unmasked", "batch", "expert_rows", "moe",
            "moe_rows"}
    rid0, step0 = mine[0]["rid"], mine[0]["step"]
    for got, want in zip(mine, theirs):
        assert set(got) == set(want) == keys
        assert got["rid"] - rid0 == want["rid"] - theirs[0]["rid"]
        assert got["step"] - step0 == want["step"] - theirs[0]["step"]
        for k in ("seq_len", "commit", "batch", "moe_rows"):
            assert got[k] == want[k], k
        for k in ("ids", "masked", "tokens", "logits", "probs", "unmasked",
                  "expert_rows"):
            assert np.array_equal(got[k], want[k]), k
        for part in range(2):
            rows = got["moe_rows"]
            assert np.array_equal(np.asarray(got["moe"][part][:, :, rows]),
                                  np.asarray(want["moe"][part][:, :, rows]))
    # a request's records are a walk through its blocks: fed what the
    # forward before left, committed ids handed out
    first = [r for r in mine if r["rid"] == rid0]
    for a, b in zip(first, first[1:]):
        if a["commit"]:
            assert b["seq_len"] == a["seq_len"] + 4 and b["masked"].all()
        else:
            assert b["seq_len"] == a["seq_len"]
            assert np.array_equal(b["masked"], a["masked"] & ~a["unmasked"])
            assert np.array_equal(
                b["ids"], np.where(a["unmasked"], a["tokens"], a["ids"]))


# -- the dispatch precedes the read -------------------------------------------

def test_the_next_block_step_opens_before_the_read_back(model, monkeypatch):
    """Over N warm block steps: ``serving.decode.dispatch`` of step K+1
    opens before ``serving.decode.readback`` of step K, N - 1 dispatches
    ran ahead and the first (after idle) in order; the block counters
    and the expert layers' add up to the in-order run's."""
    blocks = 4
    n = 3 * blocks  # 2 denoising forwards and a commit a block
    script = {0: [("submit", _P[0], 4 * blocks)]}
    _run(model, script, ahead=True)  # warm
    with _recorded_phases(monkeypatch) as opened:
        _, reqs, _, moved = _run(model, script, ahead=True)
    assert len(reqs[0].generated) == 4 * blocks
    order = [p.rsplit(".", 1)[1] for p in opened
             if p in ("serving.decode.dispatch", "serving.decode.readback")]
    assert order == ["dispatch"] + ["dispatch", "readback"] * (n - 1) \
        + ["readback"]
    assert "serving.block.unmask" not in opened  # the rule left the host
    assert opened.count("serving.block.commit") == n
    assert moved["serving.decode.ahead"] == n - 1
    assert moved["serving.decode.in_order"] == 1
    # in order, for contrast: each step is read before the next
    with _recorded_phases(monkeypatch) as opened:
        _, _, _, in_order = _run(model, script, ahead=False)
    order = [p.rsplit(".", 1)[1] for p in opened
             if p in ("serving.decode.dispatch", "serving.decode.readback")]
    assert order == ["dispatch", "readback"] * n
    assert (in_order["serving.decode.ahead"],
            in_order["serving.decode.in_order"]) == (0, n)
    for k in _COUNTED[2:]:
        assert moved[k] == in_order[k], k
    assert moved["serving.blockdiff.denoise_forwards"] == 2 * blocks
    assert moved["serving.blockdiff.blocks_committed"] == blocks
    assert moved["serving.blockdiff.tokens_unmasked"] == 4 * blocks
    assert moved["serving.decoded_tokens"] == 4 * blocks


def test_a_block_step_in_flight_counts_as_work(model):
    sched = Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                      dtype=jnp.float32)
    # warm: a dispatch that builds its program is read at once
    sched.submit(_P[0], max_new_tokens=4)
    sched.run_to_completion()
    req = sched.submit(_P[0], max_new_tokens=4)
    for _ in range(3):  # prefill + two denoising forwards + the commit
        assert sched.step() == []
    assert sched._flight is not None and sched.has_work
    assert req.generated == [] and sched.inflight() == 1
    # the commit is in flight: its four tokens are the slot's last, so
    # this step launches nothing and reads it
    out = sched.step()
    assert [t for _, t in out] == req.generated and len(out) == 4
    assert req.status == RequestStatus.DONE and not sched.has_work


# -- warm-up --------------------------------------------------------------------

def test_warmup_leaves_no_compile_for_a_run_that_admits_mid_stream(model):
    """``warmup()`` runs the block step as the loop calls it: with open
    blocks from the host, with a step's own output fed back, and with
    that merged with a fresh slot's."""
    eng = ServingEngine(model, max_batch=3, block_size=8, max_seq_len=64,
                        bucket_cap=32, temperature=0.0, background=False,
                        dtype=jnp.float32)
    eng.warmup()
    before = metrics.snapshot("xla.compile.")["xla.compile.count"]
    moved = _counters()
    handles = []
    for i, (p, n) in enumerate(zip(_P[:5], (9, 6, 7, 5, 8))):
        handles.append(eng.submit(p, max_new_tokens=n))
        for _ in range(2 + i % 2):
            eng.step()
    eng.run_until_idle()
    assert metrics.snapshot("xla.compile.")["xla.compile.count"] == before
    assert all(h.status == RequestStatus.DONE for h in handles)
    moved = {k: v - moved[k] for k, v in _counters().items()}
    # nothing was read at once for having built its program
    assert moved["serving.decode.ahead"] \
        >= 0.8 * (moved["serving.decode.ahead"]
                  + moved["serving.decode.in_order"])
    eng.close()
