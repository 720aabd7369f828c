"""Jamba (models/jamba.py): state-space layers with attention among
them, served with recurrent state beside the paged KV cache.

Everything here is held to ``benchmarks/reference/jamba_hybrid.py``, the
plain float32 full forward (no cache, no kernel), at ``JambaConfig.
tiny()``: two periods of 4 layers, attention at offset 2.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from benchmarks.reference import jamba_hybrid as reference
from paddle_tpu.inference.paged import PagedKVCache, RecurrentStateSpec
from paddle_tpu.models import Jamba, JambaConfig
from paddle_tpu.serving import Scheduler, ServingEngine

FIELDS = {"num_heads": 4, "num_kv_heads": 1, "eps": 1e-6}
# float32 on both sides; the program's sums run in another order
TOL = 2e-4


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    m = Jamba(JambaConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def weights(model):
    return reference.weights_of(model)


def _cache(model, slots=3, block=8, pages=16):
    cfg = model.config
    return PagedKVCache(
        model.kv_cache_layers, cfg.num_kv_heads, cfg.head_dim,
        num_blocks=slots * pages + 1, block_size=block,
        max_blocks_per_seq=pages, max_batch=slots, dtype=jnp.float32,
        recurrent_state=model.recurrent_state)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(3, 256, size=n)


def _slot_state(cache, slot):
    """(h [state layers, E, N], tail [state layers, K-1, E]) of a slot,
    as the reference lays them out; copies (a view would hold the
    buffer and stop its donation)."""
    with cache.pool_lock:
        return (np.array(cache.ssm_state[:, slot]).transpose(0, 2, 1),
                np.array(cache.conv_state[:, :, slot]))


def _deficit(ref_logits, toks):
    """The reference's maximum less its logit of each served token, the
    largest, as a share of the logits' scale."""
    return reference.margin_check(ref_logits, 1, toks)[0]


def test_config_derives_the_layer_kinds():
    cfg = JambaConfig()
    kinds = cfg.layers_block_type
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert (cfg.mamba_inner, cfg.head_dim) == (5120, 128)
    with pytest.raises(ValueError, match="num_experts"):
        JambaConfig(num_experts=16)


def test_the_published_model_is_three_billion_parameters():
    """Counted from the shapes, nothing built: 26 x 104.16 M + 2 x 76.68
    M + the tied embedding = 3.029 B, and 9.32 MB of state a slot."""
    from benchmarks import ops_count_ssm as ops
    import json
    import os

    fields = json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "..", "benchmarks", "configs",
        "ai21-jamba2-3b.json")))
    norms = 28 * 2 * 2560 + 2560 + 26 * (160 + 16 + 16)
    params = ops.param_bytes(fields) // 2 + norms
    assert abs(params - 3.029e9) < 1e6
    per_slot = 26 * (5120 * 16 * 4 + 5120 * 3 * 2)
    assert round(per_slot / 1e6, 2) == 9.32
    assert ops.state_update_bytes(fields, 128) == 128 * (
        2 * 5120 * 16 * 4 + 3 * 5120 * 4 + 2 * 16 * 4)


def test_initialisation_is_the_configurations(model):
    mixer = model.layers[0].mixer
    a_log = np.asarray(mixer.A_log._data)
    np.testing.assert_allclose(a_log, np.broadcast_to(
        np.log(np.arange(1, 17)), a_log.shape), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(mixer.D._data), 1.0)
    dt = np.log1p(np.exp(np.asarray(mixer.dt_proj.bias._data)))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    assert model.lm_head is None  # tied


def test_forward_is_the_references(model, weights):
    ids = _ids(40)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    want, _, _ = reference.forward(weights, FIELDS, ids, np.arange(40))
    np.testing.assert_allclose(got, want, atol=TOL * np.abs(want).max())


@pytest.mark.parametrize("mode", ["pallas", "dense"])
def test_prefill_extend_and_decode_through_both_caches(model, weights,
                                                       mode):
    """paged_prefill (a padded bucket) -> paged_prefill_extend -> decode
    steps: every token is the reference's choice over the whole
    sequence, and the slot ends in the reference's state."""
    ids = _ids(40, seed=1)
    want, h, tail = reference.forward(weights, FIELDS, ids, np.arange(40))
    cache = _cache(model)
    cache.alloc_slot(8)                    # slot 0 stays idle
    slot = cache.alloc_slot(13)
    toks = [model.paged_prefill(cache, slot, ids[:13], pad_to=32,
                                kernel_mode=mode)]
    at = [12]
    assert cache.ensure_capacity(slot, 21)
    toks.append(model.paged_prefill_extend(
        cache, slot, ids[:21], 13, 13, pad_to=16, kernel_mode=mode))
    at.append(20)
    last = np.zeros((3,), np.int64)
    active = np.zeros((3,), bool)
    active[slot] = True
    for p in range(21, 40):
        last[slot] = ids[p]
        assert cache.ensure_capacity(slot, p + 1)
        toks.append(int(np.asarray(model.paged_decode_step(
            cache, last, active, kernel_mode=mode))[slot]))
        at.append(p)
    assert _deficit(want[at], toks) < TOL
    got_h, got_tail = _slot_state(cache, slot)
    assert reference.rel_rms(got_h, h) < TOL
    assert reference.rel_rms(got_tail, tail) < TOL


@pytest.mark.parametrize("pad_to", [16, 64])
def test_a_padded_bucket_gives_the_state_of_the_true_length(
        model, weights, pad_to):
    ids = _ids(11, seed=2)
    _, h, tail = reference.forward(weights, FIELDS, ids, np.arange(11))
    cache = _cache(model)
    slot = cache.alloc_slot(11)
    model.paged_prefill(cache, slot, ids, pad_to=pad_to,
                        kernel_mode="pallas")
    got_h, got_tail = _slot_state(cache, slot)
    assert reference.rel_rms(got_h, h) < TOL
    assert reference.rel_rms(got_tail, tail) < TOL


@pytest.mark.parametrize("mode", ["pallas", "dense"])
def test_an_inactive_slots_state_is_bit_identical_after_a_step(model,
                                                               mode):
    cache = _cache(model)
    slots = [cache.alloc_slot(9) for _ in range(3)]
    for s in slots:
        model.paged_prefill(cache, s, _ids(9, seed=s), kernel_mode=mode)
    before = [_slot_state(cache, s) for s in slots]
    active = np.array([True, False, True])
    for s in slots:
        assert cache.ensure_capacity(s, 10)
    model.paged_decode_step(cache, np.array([5, 6, 7]), active,
                            kernel_mode=mode)
    after = [_slot_state(cache, s) for s in slots]
    for part in (0, 1):
        np.testing.assert_array_equal(after[1][part], before[1][part])
        assert not np.array_equal(after[0][part], before[0][part])
    assert list(cache.seq_lens) == [10, 9, 10]


def test_extend_refuses_a_tail_the_state_does_not_stand_at(model):
    cache = _cache(model)
    slot = cache.alloc_slot(20)
    model.paged_prefill(cache, slot, _ids(12))
    with pytest.raises(ValueError, match="recurrent state stands at"):
        model.paged_prefill_extend(cache, slot, _ids(20), 8, 8)
    # a slot that was just allocated stands at 0: extend is a prefill
    fresh = cache.alloc_slot(12)
    tok = model.paged_prefill_extend(cache, fresh, _ids(12), 0, 0)
    assert tok == model.paged_prefill(cache, cache.alloc_slot(12),
                                      _ids(12))


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    return ServingEngine(model, temperature=0.0, dtype=jnp.float32,
                         block_size=8, max_seq_len=64, bucket_cap=64,
                         paged_kernel="pallas", **kw)


def _served_right(weights, prompt, toks):
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int64)
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
    want, _, _ = reference.forward(weights, FIELDS, seq, rows)
    return _deficit(want, [int(t) for t in toks]) < TOL


def test_slot_reuse_never_leaks_the_last_requests_state(model, weights):
    """One slot, two different requests through it, one after the
    other: each is the reference's from zero state."""
    with _engine(model, max_batch=1) as eng:
        for seed, n in ((4, 17), (5, 9)):
            prompt = _ids(n, seed=seed)
            toks = eng.submit(prompt, max_new_tokens=12).result(timeout=300)
            assert _served_right(weights, prompt, toks)
        assert eng.cache.state_fresh.tolist() == [True] or \
            not eng.cache._live[0]


def test_preempt_then_readmit_prefills_from_zero_state(model, weights):
    """A pool too small for two growing requests: one is preempted,
    frees its slot and is prefilled again (prompt + what it generated)
    from zero state; every token of both is still the reference's."""
    from paddle_tpu.profiler import metrics

    sched = Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                      num_blocks=8, dtype=jnp.float32, bucket_cap=64,
                      paged_kernel="pallas")
    before = metrics.snapshot("serving.preempt")
    prompts = [_ids(14, seed=6), _ids(15, seed=7)]
    reqs = [sched.submit(p, max_new_tokens=24) for p in prompts]
    sched.run_to_completion()
    assert metrics.snapshot("serving.preempt")["serving.preempt"] \
        > before.get("serving.preempt", 0)
    assert sum(r.preempts for r in reqs) >= 1
    for prompt, req in zip(prompts, reqs):
        assert req.status == "DONE" and len(req.generated) == 24
        assert _served_right(weights, prompt, np.asarray(req.generated))


def test_a_cache_with_state_plans_no_prefix_hit(model):
    cache = _cache(model)
    ids = _ids(24)
    slot = cache.alloc_slot_cached(cache.plan_prefix(ids))
    model.paged_prefill(cache, slot, ids)
    cache.commit_prefix(slot, cache.plan_prefix(ids))
    plan = cache.plan_prefix(ids)   # the same prompt again: still no hit
    assert (plan.covered_tokens, plan.hit_blocks, plan.digests) == (0, 0, [])
    assert plan.chunks_total == 3 and plan.tail_start == 0
    assert not cache._prefix_index and not cache._partial_index
    cache.free_slot(slot)
    assert cache.num_cached_blocks() == 0


def test_served_twice_the_same_prompt_gives_the_same_right_tokens(
        model, weights):
    """The prefix cache is on by default: with recurrent state it must
    not hand the second request the first one's blocks."""
    prompt = _ids(20, seed=8)
    with _engine(model) as eng:
        assert eng.scheduler.prefix_cache
        first = eng.submit(prompt, max_new_tokens=8).result(timeout=300)
        second = eng.submit(prompt, max_new_tokens=8).result(timeout=300)
    assert list(first) == list(second)
    assert _served_right(weights, prompt, first)


def test_what_cannot_carry_state_is_refused_with_the_reason(model):
    from paddle_tpu.serving import kv_transfer
    from paddle_tpu.serving.mesh import ServingMesh

    with pytest.raises(ValueError, match="recurrent state"):
        Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                  dtype=jnp.float32, spec=True)
    with pytest.raises(ValueError, match="serving mesh"):
        Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                  dtype=jnp.float32, mesh=ServingMesh(1, 2))
    with pytest.raises(ValueError, match="int8 KV"):
        model.paged_prefill(PagedKVCache(
            2, 1, 8, num_blocks=9, block_size=8, max_blocks_per_seq=4,
            max_batch=2, dtype=jnp.float32, kv_dtype="int8",
            recurrent_state=model.recurrent_state), 0, _ids(5))
    sched = Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                      dtype=jnp.float32)
    with pytest.raises(ValueError, match="prefill_only"):
        sched.submit(_ids(9), max_new_tokens=4, prefill_only=True)
    with pytest.raises(ValueError, match="recurrent state"):
        kv_transfer.export_prefix(sched.cache, _ids(16))
    with pytest.raises(ValueError, match="recurrent state"):
        kv_transfer.import_prefix(sched.cache, b"")
    from paddle_tpu.serving.scheduler import HandoffError
    with pytest.raises(HandoffError, match="recurrent state"):
        sched.admit_handoff(_ids(9), 5)
    with pytest.raises(ValueError, match="one device"):
        PagedKVCache(2, 1, 8, num_blocks=9, block_size=8,
                     max_blocks_per_seq=4, max_batch=2, num_slices=2,
                     recurrent_state=model.recurrent_state)


def test_a_cache_built_for_another_model_is_refused(model):
    plain = PagedKVCache(8, 1, 8, num_blocks=9, block_size=8,
                         max_blocks_per_seq=4, max_batch=2,
                         dtype=jnp.float32)
    with pytest.raises(ValueError, match="not built for this model"):
        model.paged_decode_step(plain, np.zeros(2, np.int64),
                                np.ones(2, bool))


def test_the_state_is_counted_and_donated(model):
    """``pool_bytes`` counts the state; a step takes pools and state
    donated and the cache rebinds what comes back; the counters say how
    many slots' state the steps updated and how many true tokens the
    scans saw."""
    from paddle_tpu.profiler import metrics

    spec = model.recurrent_state
    assert spec == RecurrentStateSpec(layers=6, channels=64, states=16,
                                      conv_tail=3)
    before = metrics.snapshot("serving.")
    sched = Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                      dtype=jnp.float32, paged_kernel="pallas")
    cache = sched.cache
    state = 6 * 2 * (16 * 64 * 4 + 3 * 64 * 4)
    assert cache.state_bytes() == state
    assert cache.pool_bytes() == state + 2 * 2 * cache.num_blocks * 8 * 8 * 4
    assert cache.num_layers == 2
    handed = cache.ssm_state
    reqs = [sched.submit(_ids(n, seed=n), max_new_tokens=6)
            for n in (10, 7)]
    sched.run_to_completion()
    assert handed.is_deleted() and not cache.ssm_state.is_deleted()
    delta = {k: v - before.get(k, 0) for k, v in
             metrics.snapshot("serving.").items()
             if isinstance(v, (int, float))}
    assert delta["serving.kv.copied_calls"] == 0
    assert delta["serving.ssm.scan_tokens"] == 17
    # 5 decode steps of 2 live slots: the prefill gave the first token
    assert delta["serving.ssm.state_slot_steps"] == 10
    assert metrics.snapshot("serving.ssm.state_bytes")[
        "serving.ssm.state_bytes"] == state
    assert all(r.status == "DONE" for r in reqs)


def test_a_decode_step_reports_what_a_slots_recurrence_was_fed(model,
                                                             weights):
    """``state_observer``: under the cache's lock a step appends, for
    the watched slot, each state layer's (delta, c, B, active); the
    float32 recurrence replayed over those from the slot's state before
    ends in the slot's state after."""
    cache = _cache(model)
    slots = [cache.alloc_slot(9) for _ in range(2)]
    for s in slots:
        model.paged_prefill(cache, s, _ids(9, seed=20 + s))
    before, _ = _slot_state(cache, 1)
    fed = []
    active = np.array([True, True, False])
    for step in range(5):
        for s in slots:
            assert cache.ensure_capacity(s, 10 + step)
        model.paged_decode_step(cache, np.array([5 + step, 6, 0]), active,
                                kernel_mode="pallas",
                                state_observer=lambda: (1, fed))
    model.paged_decode_step(cache, np.array([1, 1, 0]),
                            np.array([True, False, False]),
                            state_observer=lambda: (1, fed))
    fed = np.stack([np.asarray(f) for f in fed])
    assert fed.shape == (6, 6, 2 * 64 + 16 + 1)
    assert fed[:5, :, -1].all() and not fed[5, :, -1].any()
    after, _ = _slot_state(cache, 1)
    assert reference.rel_rms(
        after, reference.replay(weights, before, fed[:5])) < 1e-6
    assert reference.rel_rms(after, reference.replay(
        weights, before, fed[:5], h_bits=7)) > 1e-4


def test_the_decode_loop_runs_a_step_ahead_with_state(model, weights):
    """The run-ahead loop is the Llama file's: steps are dispatched with
    the step before unread, and the tokens are still the reference's."""
    from paddle_tpu.profiler import metrics

    before = metrics.snapshot("serving.decode.")
    sched = Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                      dtype=jnp.float32, paged_kernel="pallas")
    prompt = _ids(12, seed=9)
    sched.submit(_ids(5, seed=10), max_new_tokens=3)   # warms the step
    sched.run_to_completion()
    req = sched.submit(prompt, max_new_tokens=16)
    sched.run_to_completion()
    after = metrics.snapshot("serving.decode.")
    assert after["serving.decode.ahead"] - before.get(
        "serving.decode.ahead", 0) >= 12
    assert _served_right(weights, prompt, np.asarray(req.generated))
