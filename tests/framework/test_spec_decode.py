"""Self-speculative decoding (ISSUE 14 tentpole B): prompt-lookup
drafts + the batched multi-position verify sweep inside the
continuous-batching step (serving/spec.py, Scheduler._decode_spec,
Llama.paged_spec_step).

The contract under test, in order of importance:
- greedy outputs are BIT-IDENTICAL spec-on vs spec-off — including
  under preemption, prefix-cache hits, eos mid-acceptance, and int8
  KV pools (the compounding tier);
- rejected draft rows roll back: after every speculative step each
  running slot holds exactly ceil(seq_len / block_size) blocks, and a
  drained engine returns the whole pool;
- serving.spec.{proposed,accepted,rejected} counters + the
  accept-rate histogram move when armed and stay silent when
  FLAGS_serving_spec is off;
- accepted-vs-wasted draft positions bill through PR 9's cost
  attribution (CostReport.spec_* + the closure property).
"""

import math

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving.spec import propose_draft, repetitive_prompts

BS = 8  # block size every engine in this file uses


# ---------------------------------------------------------------------------
# proposer unit tests (pure host)
# ---------------------------------------------------------------------------

def test_propose_draft_cycle():
    # trailing 3-gram [3,1,2] recurs; the continuation of its most
    # recent PRIOR occurrence is proposed
    ctx = [1, 2, 3, 1, 2, 3, 1, 2]
    assert propose_draft(ctx, 3).tolist() == [3, 1, 2]
    assert propose_draft(ctx, 5).tolist() == [3, 1, 2]  # runs off the end
    assert propose_draft(ctx, 1).tolist() == [3]        # cap honored


def test_propose_draft_ngram_fallback():
    # no 3- or 2-gram repeats, but the last TOKEN was seen: 1-gram
    # fallback proposes what followed it
    assert propose_draft([7, 5, 7], 4).tolist() == [5, 7]


def test_propose_draft_most_recent_occurrence_wins():
    # [9, 1, 9, 2, 9]: token 9 occurred at 0 and 2; recency means the
    # draft is what followed position 2 (-> 2), not position 0 (-> 1)
    assert propose_draft([9, 1, 9, 2, 9], 1).tolist() == [2]


def test_propose_draft_nothing_to_exploit():
    assert propose_draft([1, 2, 3, 4, 5], 4).size == 0   # no repeats
    assert propose_draft([5], 4).size == 0               # too short
    assert propose_draft([1, 2, 1], 0).size == 0         # zero budget


# ---------------------------------------------------------------------------
# scheduler integration
# ---------------------------------------------------------------------------

# tiny_llama fixture + the pinned engine config come from conftest.py
# so this file, test_quantization.py, and tools/spec_gate.py measure
# the same engine
from conftest import tiny_engine as _engine  # noqa: E402


def _run(model, prompts, max_new=10, **kw):
    eng = _engine(model, **kw)
    hs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_idle()
    outs = [h.tokens() for h in hs]
    eng.close()
    return outs, hs


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, size=s) for s in sizes]


def test_spec_greedy_bit_identical(tiny_llama):
    prompts = _prompts(0, [9, 5, 14, 7])
    base, _ = _run(tiny_llama, prompts)
    spec, _ = _run(tiny_llama, prompts, spec=True)
    assert spec == base


def test_spec_flag_routing(tiny_llama):
    from paddle_tpu.serving import Scheduler
    saved = paddle.get_flags(["FLAGS_serving_spec",
                              "FLAGS_serving_spec_tokens"])
    try:
        paddle.set_flags({"FLAGS_serving_spec": True,
                          "FLAGS_serving_spec_tokens": 6})
        s = Scheduler(tiny_llama, max_batch=2, block_size=BS,
                      max_seq_len=64)
        assert s.spec and s.spec_tokens == 6
        # ctor kwarg beats the flag
        s2 = Scheduler(tiny_llama, max_batch=2, block_size=BS,
                       max_seq_len=64, spec=False)
        assert not s2.spec
    finally:
        paddle.set_flags(saved)
    # greedy-only: any sampling temperature disables the tier
    warm = Scheduler(tiny_llama, max_batch=2, block_size=BS,
                     max_seq_len=64, spec=True, temperature=0.7)
    assert not warm.spec


# A prompt whose greedy continuation (for THIS seed-0 tiny model) is
# self-repetitive, so the prompt-lookup proposer stays productive —
# the first member of the shared high-acceptance corpus that
# tools/spec_gate.py, bench.py, and serve_llm.py --spec all measure.
_REPETITIVE_PROMPT = repetitive_prompts()[0]


def test_spec_counters_and_acceptance(tiny_llama):
    from paddle_tpu.profiler import metrics
    prompt = _REPETITIVE_PROMPT
    before = metrics.snapshot("serving.spec.")
    outs, hs = _run(tiny_llama, [prompt], max_new=12, spec=True)
    after = metrics.snapshot("serving.spec.")
    proposed = after["serving.spec.proposed"] - \
        before["serving.spec.proposed"]
    accepted = after["serving.spec.accepted"] - \
        before["serving.spec.accepted"]
    rejected = after["serving.spec.rejected"] - \
        before["serving.spec.rejected"]
    assert proposed > 0
    assert 0 <= accepted <= proposed
    assert rejected == proposed - accepted
    assert after["serving.spec.steps"] > before["serving.spec.steps"]
    assert after["serving.spec.accept_rate"]["count"] > \
        before["serving.spec.accept_rate"]["count"]
    # and the run still matches plain decode
    base, _ = _run(tiny_llama, [prompt], max_new=12)
    assert outs == base


def test_spec_off_counter_silence(tiny_llama):
    from paddle_tpu.profiler import metrics
    before = metrics.snapshot("serving.spec.")
    _run(tiny_llama, _prompts(1, [8, 6]))  # default: spec off
    assert metrics.snapshot("serving.spec.") == before


def test_spec_under_preemption(tiny_llama):
    """Speculation + pool exhaustion: preempted victims re-prefill and
    the whole run stays bit-identical to uncontended spec-off decode."""
    from paddle_tpu.profiler import metrics
    prompts = _prompts(2, [9, 8])
    refs = [_run(tiny_llama, [p], max_new=10)[0][0] for p in prompts]
    p0 = metrics.snapshot()["serving.preempt"]
    tight, _ = _run(tiny_llama, prompts, max_new=10, spec=True,
                    max_batch=2, num_blocks=6)
    assert tight == refs
    assert metrics.snapshot()["serving.preempt"] > p0


def test_spec_with_prefix_cache_hits(tiny_llama):
    """Cache-hitting admissions (tail-extend prefill) feed the same
    speculative decode; outputs match the uncontended references."""
    rng = np.random.default_rng(3)
    system = rng.integers(3, 250, size=24)
    prompts = [np.concatenate([system, rng.integers(3, 250, size=4)])
               for _ in range(3)]
    refs = [_run(tiny_llama, [p])[0][0] for p in prompts]
    from paddle_tpu.profiler import metrics
    h0 = metrics.snapshot()["serving.prefix.hit_blocks"]
    shared, _ = _run(tiny_llama, prompts, spec=True)
    assert shared == refs
    assert metrics.snapshot()["serving.prefix.hit_blocks"] > h0


def test_spec_quant_compose(tiny_llama):
    """The two tiers compound: spec-on int8 == spec-off int8."""
    prompts = _prompts(4, [9, 6, 12])
    q, _ = _run(tiny_llama, prompts, kv_cache_dtype="int8")
    qs, _ = _run(tiny_llama, prompts, kv_cache_dtype="int8", spec=True)
    assert qs == q


def test_spec_eos_mid_acceptance(tiny_llama):
    """A draft run that crosses eos truncates: both modes stop at the
    same token with identical outputs (accepted rows past eos are
    discarded like sequential decode never produced them)."""
    prompt = _REPETITIVE_PROMPT
    base, _ = _run(tiny_llama, [prompt], max_new=12)
    eos = base[0][4]  # a token the greedy run provably emits
    ref, _ = _run(tiny_llama, [prompt], max_new=12, eos_token_id=eos)
    spec, _ = _run(tiny_llama, [prompt], max_new=12, eos_token_id=eos,
                   spec=True)
    assert spec == ref
    assert spec[0][-1] == eos and len(spec[0]) < 12


def test_spec_rollback_block_accounting(tiny_llama):
    """After EVERY speculative step each running slot holds exactly
    ceil(seq_len / block_size) blocks — rejected rows' fresh growth
    went back to the pool — and a drained engine returns everything."""
    eng = _engine(tiny_llama, spec=True)
    sched = eng.scheduler
    cache = sched.cache
    usable = cache.num_blocks - 1
    for p in _prompts(5, [9, 5, 12]):
        eng.submit(p, max_new_tokens=10)
    spec_steps = 0
    from paddle_tpu.profiler import metrics
    while sched.has_work:
        s0 = metrics.snapshot()["serving.spec.steps"]
        eng.step()
        spec_steps += metrics.snapshot()["serving.spec.steps"] - s0
        for slot in sched.running:
            want = max(math.ceil(int(cache.seq_lens[slot]) / BS), 1)
            assert len(cache._slot_blocks[slot]) == want, \
                (slot, int(cache.seq_lens[slot]),
                 len(cache._slot_blocks[slot]))
    assert spec_steps > 0  # the invariant was actually exercised
    occ = cache.occupancy()
    assert occ["active"] == 0
    assert occ["free"] + occ["cached_free"] == usable
    eng.close()


def test_spec_cost_billing(tiny_llama):
    """Wasted draft positions bill real device time (apportionment
    weight 1 + proposed), emitted tokens count what streamed, and the
    PR 9 closure property survives speculative steps."""
    eng = _engine(tiny_llama, spec=True)
    prompt = _REPETITIVE_PROMPT
    h = eng.submit(prompt, max_new_tokens=24)
    eng.run_until_idle()
    cost = h.cost()
    assert cost is not None
    assert cost.spec_proposed >= cost.spec_accepted >= 0
    assert cost.spec_proposed > 0
    assert cost.tokens_emitted == len(h.tokens())
    for entry in eng.scheduler.accounting.step_log:
        assert abs(entry["attributed_us"] + entry["compile_us"]
                   + entry["idle_us"] - entry["step_us"]) < 1e-3
    eng.close()


def test_spec_warmup_includes_verify_program(tiny_llama):
    """warmup() precompiles the spec sweep: the first live speculative
    step triggers zero XLA compiles."""
    from paddle_tpu.profiler import metrics
    eng = _engine(tiny_llama, spec=True, ready=False)
    eng.warmup()
    prompt = _REPETITIVE_PROMPT
    c0 = metrics.snapshot()["xla.compile.count"]
    h = eng.submit(prompt, max_new_tokens=8)
    eng.run_until_idle()
    assert metrics.snapshot()["xla.compile.count"] == c0
    assert h.status == "DONE"
    eng.close()


# -- the verify sweep writes the donated pools in place (ISSUE 27) ---------

from conftest import (assert_lowered_donates, assert_pools_equal,  # noqa: E402
                      pools_numpy, undonated_twin)


def _two_prefilled_slots(model, kv_dtype):
    import jax.numpy as jnp

    from paddle_tpu.inference.paged import PagedKVCache

    cfg = model.config
    c = PagedKVCache(cfg.num_layers, cfg.num_kv_heads,
                     cfg.hidden_size // cfg.num_heads, num_blocks=20,
                     block_size=8, max_blocks_per_seq=4, max_batch=3,
                     dtype=jnp.float32, kv_dtype=kv_dtype)
    last = np.zeros((3,), np.int64)
    for prompt in _prompts(27, [11, 6]):
        slot = c.alloc_slot(len(prompt) + 4)
        last[slot] = model.paged_prefill(c, slot, prompt)
    return c, last


@pytest.mark.filterwarnings("error::UserWarning")
@pytest.mark.parametrize("kv_dtype", [None, "int8"],
                         ids=["speculative", "speculative-int8"])
def test_verify_sweep_writes_the_donated_pools_like_the_eager_reference(
        tiny_llama, kv_dtype):
    """1 + 3 candidate positions a slot (slot 1 has two real drafts,
    slot 2 is idle): the sweep consumes its pools, agrees bitwise with
    its undonated twin, and its output is the eager ``paged_spec_write``
    of the rows it wrote — padding and the idle slot touch nothing but
    the null block."""
    import jax.numpy as jnp

    from paddle_tpu.inference.paged import paged_spec_write
    from paddle_tpu.quantization import dequantize_rows

    model = tiny_llama
    cache, last = _two_prefilled_slots(model, kv_dtype)
    ref, _ = _two_prefilled_slots(model, kv_dtype)
    drafts = np.array([[7, 8, 9], [4, 5, 0], [0, 0, 0]], np.int64)
    n_inputs = np.array([4, 3, 4], np.int64)
    active = np.array([True, True, False])

    handed_in = cache.pool_arrays()
    out = np.asarray(model.paged_spec_step(cache, last, drafts, n_inputs,
                                           active))
    assert all(a.is_deleted() for a in handed_in)

    toks = np.concatenate([last.reshape(-1, 1), drafts], axis=1)
    lens = jnp.asarray(ref.seq_lens)
    program, args = model.paged_call_args(
        ref, "spec",
        (jnp.asarray(toks, jnp.int32), lens,
         jnp.asarray(n_inputs, jnp.int32), jnp.asarray(active),
         ref.block_tables))
    old = ref.pool_arrays()
    try:
        assert_lowered_donates(program._jitted.lower(*args), (6, 7, 8, 9))
        ref_out, *new = undonated_twin(program)(*args)
    finally:
        model._param_rebind()(args[0])
    assert not any(a.is_deleted() for a in old)
    ref.rebind_pools(*new)
    assert np.array_equal(out[active], np.asarray(ref_out)[active])
    assert_pools_equal(pools_numpy(cache), pools_numpy(ref))

    new = ref.pool_arrays()
    n = ref.num_layers
    pos = ref.seq_lens[:, None] + np.arange(4)[None, :]
    blocks = ref.block_tables[np.arange(3)[:, None], pos // 8]
    for i in range(n):
        if kv_dtype:
            k, v = (dequantize_rows(new[j][blocks, pos % 8],
                                    new[2 * n + j][blocks, pos % 8],
                                    jnp.float32) for j in (i, n + i))
            want = paged_spec_write(
                old[i], old[n + i], jnp.asarray(ref.block_tables), lens,
                k, v, jnp.asarray(n_inputs, jnp.int32),
                jnp.asarray(active), k_scale=old[2 * n + i],
                v_scale=old[3 * n + i])
            got = (new[i], new[n + i], new[2 * n + i], new[3 * n + i])
        else:
            want = paged_spec_write(
                old[i], old[n + i], jnp.asarray(ref.block_tables), lens,
                new[i][blocks, pos % 8], new[n + i][blocks, pos % 8],
                jnp.asarray(n_inputs, jnp.int32), jnp.asarray(active))
            got = (new[i], new[n + i])
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w)), i
