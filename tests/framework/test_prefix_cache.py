"""Prefix caching + copy-on-write block sharing in the paged KV pool
(docs/SERVING.md "Prefix caching").

Pins the sharing contract end to end: rolling chunk hashes, hit/miss/
partial-coverage admission, COW on divergence-inside-a-shared-block and
on decode-append-into-a-shared-tail (both bit-identical to uncontended
decode), the refcount lifecycle (free -> cached -> evicted -> reused),
eviction-before-preemption ordering, uncovered-token admission budgets,
bucket padding never poisoning a content hash, and the
`FLAGS_serving_prefix_cache`/`prefix_cache=False` revert to private
blocks.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.paged import (CapacityError,
                                        ContinuousBatchingEngine,
                                        PagedKVCache, chunk_digests)
from paddle_tpu.models import Llama, LlamaConfig
from paddle_tpu.profiler import metrics
from paddle_tpu.serving import RequestStatus, ServingEngine


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


def _ref_tokens(model, prompt, n, *, block_size=8, max_seq_len=64):
    """Uncontended greedy reference via the base engine (no sharing)."""
    eng = ContinuousBatchingEngine(model, max_batch=2,
                                   block_size=block_size,
                                   max_seq_len=max_seq_len,
                                   temperature=0.0)
    rid = eng.add_request(prompt, max_new_tokens=n)
    return eng.run_to_completion()[rid]


def _snap():
    return metrics.snapshot("serving.")


# -- content hashing ----------------------------------------------------


def test_chunk_digests_rolling():
    ids = np.arange(40, dtype=np.int64)
    d = chunk_digests(ids, 16)
    assert len(d) == 2  # only FULL chunks hash; the 8-token tail doesn't
    # a digest identifies the whole prefix: flipping token 0 moves BOTH
    flipped = ids.copy()
    flipped[0] += 1
    d2 = chunk_digests(flipped, 16)
    assert d[0] != d2[0] and d[1] != d2[1]
    # flipping a token in chunk 1 leaves chunk 0's digest alone
    late = ids.copy()
    late[20] += 1
    d3 = chunk_digests(late, 16)
    assert d3[0] == d[0] and d3[1] != d[1]
    # dtype canonicalization: int32 vs int64 token arrays hash equal
    assert chunk_digests(ids.astype(np.int32), 16) == d


# -- ensure_capacity failure reasons (satellite) ------------------------


def test_capacity_error_reasons():
    c = PagedKVCache(1, 2, 16, num_blocks=4, block_size=4,
                     max_blocks_per_seq=2, max_batch=2)
    s0 = c.alloc_slot(8)  # both of its table entries
    r = c.ensure_capacity(s0, 9)
    assert not r and r.reason == CapacityError.SEQ_LIMIT
    s1 = c.alloc_slot(4)  # last usable block
    r = c.ensure_capacity(s1, 8)
    assert not r and r.reason == CapacityError.BLOCKS
    assert bool(c.ensure_capacity(s1, 4)) is True


# -- plan / refcount lifecycle on a bare cache --------------------------


def test_plan_and_refcount_lifecycle(model):
    rng = np.random.default_rng(20)
    prompt = rng.integers(0, 255, (20,)).astype("int64")  # 2 full + 4
    eng = ContinuousBatchingEngine(model, max_batch=2, block_size=8,
                                   max_seq_len=64, temperature=0.0)
    c = eng.cache
    # cold plan: nothing matches
    plan = c.plan_prefix(prompt)
    assert plan.matched_full == 0 and plan.covered_tokens == 0
    assert plan.chunks_total == 3
    slot = c.alloc_slot_cached(plan)
    model.paged_prefill(c, slot, prompt, temperature=0.0)
    c.commit_prefix(slot, plan)
    blocks = list(c._slot_blocks[slot])
    # warm plan: both full chunks + the exact partial tail match
    plan2 = c.plan_prefix(prompt)
    assert plan2.matched_full == 2
    assert plan2.matched_blocks == blocks[:2]
    assert plan2.partial_block == blocks[2] and plan2.partial_shared
    assert plan2.covered_tokens == 20
    assert plan2.tail_start == 19 and plan2.write_start == 20
    # a diverging second chunk matches only chunk 0
    div = prompt.copy()
    div[10] += 1
    pd = c.plan_prefix(div)
    assert pd.matched_full == 1 and pd.covered_tokens == 8
    assert pd.partial_block is None
    # free -> registered blocks park reclaimable-cached, not free-free
    c.free_slot(slot)
    assert c.num_cached_blocks() == 3  # 2 full + 1 partial registered
    assert c.num_free_blocks() == c.num_blocks - 1  # still allocatable
    assert all(c._refcount[b] == 0 for b in blocks)
    # re-alloc by content: cached blocks map straight back (refcount 1)
    plan3 = c.plan_prefix(prompt)
    slot2 = c.alloc_slot_cached(plan3)
    assert list(c._slot_blocks[slot2]) == blocks
    assert all(c._refcount[b] == 1 for b in blocks)
    assert c.num_cached_blocks() == 0
    c.free_slot(slot2)
    # eviction on demand: allocations beyond the free list reclaim LRU
    # cached blocks and drop their index entries (16 usable = 13 free +
    # 3 cached here; two 8-block slots need all 16)
    before = _snap()["serving.prefix.evictions"]
    big1 = c.alloc_slot(64)
    big2 = c.alloc_slot(64)
    assert big1 is not None and big2 is not None
    assert _snap()["serving.prefix.evictions"] == before + 3
    # the evicted content no longer matches, and its blocks were reused
    plan4 = c.plan_prefix(prompt)
    assert plan4.covered_tokens == 0
    c.free_slot(big1)
    c.free_slot(big2)


def test_prepare_append_cow_unit(model):
    """Two slots sharing a partially-filled block: the first appender
    copies; the second (now sole sharer) appends in place."""
    rng = np.random.default_rng(21)
    prompt = rng.integers(0, 255, (20,)).astype("int64")
    eng = ContinuousBatchingEngine(model, max_batch=2, block_size=8,
                                   max_seq_len=64, temperature=0.0)
    c = eng.cache
    s0 = c.alloc_slot_cached(c.plan_prefix(prompt))
    model.paged_prefill(c, s0, prompt, temperature=0.0)
    c.commit_prefix(s0, c.plan_prefix(prompt))
    plan = c.plan_prefix(prompt)
    s1 = c.alloc_slot_cached(plan)
    model.paged_prefill_extend(c, s1, prompt, plan.tail_start,
                               plan.write_start, temperature=0.0)
    tail = c._slot_blocks[s0][2]
    assert c._slot_blocks[s1][2] == tail and c._refcount[tail] == 2
    before = _snap()["serving.prefix.cow_copies"]
    assert c.prepare_append(s0, 21)  # append into the shared tail: COW
    assert _snap()["serving.prefix.cow_copies"] == before + 1
    assert c._slot_blocks[s0][2] != tail
    assert c._refcount[tail] == 1  # s1 remains the only sharer
    assert c.prepare_append(s1, 21)  # sole sharer: in place, no copy
    assert _snap()["serving.prefix.cow_copies"] == before + 1
    assert c._slot_blocks[s1][2] == tail


# -- admission: hits, partial coverage, bit-exactness -------------------


def test_shared_prefix_hit_and_greedy_bit_exact(model):
    """Requests sharing a long system prompt admit via the extend
    program (covered blocks mapped, zero prefill compute) and their
    greedy outputs are bit-identical to uncontended runs."""
    rng = np.random.default_rng(22)
    system = rng.integers(0, 255, (24,)).astype("int64")  # 3 chunks @ 8
    prompts = [np.concatenate([system,
                               rng.integers(0, 255, (3 + i,))
                               .astype("int64")])
               for i in range(4)]
    refs = [_ref_tokens(model, p, 6) for p in prompts]
    before = _snap()
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False)
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    after = _snap()
    for h, ref in zip(handles, refs):
        assert h.status == RequestStatus.DONE
        assert h.tokens() == ref
    # requests 2..4 each mapped the 3 system-prompt blocks
    hits = after["serving.prefix.hit_blocks"] - \
        before["serving.prefix.hit_blocks"]
    assert hits >= 9
    assert after["serving.prefix.computed_tokens"] > \
        before["serving.prefix.computed_tokens"]


def test_cow_on_shared_tail_append_bit_exact(model):
    """Exact-duplicate prompts share EVERYTHING including the partial
    tail block; the first decode append into it copies-on-write, and
    both requests still emit the uncontended greedy tokens."""
    rng = np.random.default_rng(23)
    p = rng.integers(0, 255, (20,)).astype("int64")  # 2 full + 4 partial
    ref = _ref_tokens(model, p, 8)
    before = _snap()
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False)
    h1 = eng.submit(p, max_new_tokens=8)
    h2 = eng.submit(p.copy(), max_new_tokens=8)
    eng.run_until_idle()
    after = _snap()
    assert h1.tokens() == ref
    assert h2.tokens() == ref
    assert after["serving.prefix.cow_copies"] > \
        before["serving.prefix.cow_copies"]
    # the duplicate covered its whole prompt: 2 full + 1 partial block
    assert after["serving.prefix.hit_blocks"] >= \
        before["serving.prefix.hit_blocks"] + 3


def test_cow_on_divergence_extension_bit_exact(model):
    """A prompt that extends another's partially-filled tail block
    copies it at admission (writes would land mid-prefix) and decodes
    bit-identically."""
    rng = np.random.default_rng(24)
    a = rng.integers(0, 255, (20,)).astype("int64")
    b = np.concatenate([a, rng.integers(0, 255, (9,)).astype("int64")])
    ref_a = _ref_tokens(model, a, 6)
    ref_b = _ref_tokens(model, b, 6)
    before = _snap()
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False)
    ha = eng.submit(a, max_new_tokens=6)
    eng.step()  # admit + register a's chunks before b plans
    hb = eng.submit(b, max_new_tokens=6)
    eng.run_until_idle()
    after = _snap()
    assert ha.tokens() == ref_a
    assert hb.tokens() == ref_b
    assert after["serving.prefix.cow_copies"] > \
        before["serving.prefix.cow_copies"]


def test_bucket_padding_never_poisons_hashes(model):
    """Hashes cover REAL tokens only: a 10-token prompt that buckets to
    16 registers one full chunk (its real first 8 tokens) plus a 2-token
    partial — never a 16-token chunk containing bucket padding, even
    though the prefill wrote padded KV rows into the pool. A second
    prompt equal to the padded form shares only real content."""
    rng = np.random.default_rng(25)
    a = rng.integers(1, 255, (10,)).astype("int64")     # pads to 16
    b = np.concatenate([a, np.zeros(6, np.int64)])      # len 16, real 0s
    ref_b = _ref_tokens(model, b, 6)
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False)
    ha = eng.submit(a, max_new_tokens=6)
    eng.step()
    plan = eng.cache.plan_prefix(b)
    # chunk 0 (8 real shared tokens) is a legitimate hit; b's SECOND
    # chunk — which equals a's padded form — must not be full-matched:
    # a registered only its 2 real tail tokens there
    assert plan.matched_full == 1
    assert plan.digests[1] not in eng.cache._prefix_index
    assert plan.partial_len == 2 and not plan.partial_shared
    hb = eng.submit(b, max_new_tokens=6)
    eng.run_until_idle()
    assert hb.tokens() == ref_b
    assert ha.status == RequestStatus.DONE


def test_admission_budget_counts_uncovered_tokens(model):
    """Cache-hitting requests charge the prefill budget for their
    uncovered tail only: two warm 26-token prompts fit one 8-token
    budget step together (raw lengths would not)."""
    rng = np.random.default_rng(26)
    system = rng.integers(0, 255, (24,)).astype("int64")
    mk = lambda: np.concatenate(  # noqa: E731
        [system, rng.integers(0, 255, (2,)).astype("int64")])
    eng = ServingEngine(model, max_batch=4, block_size=8, max_seq_len=64,
                        temperature=0.0, prefill_token_budget=8,
                        background=False)
    eng.submit(mk(), max_new_tokens=2)
    eng.run_until_idle()  # warm: registers the system prompt's 3 chunks
    eng.submit(mk(), max_new_tokens=2)
    eng.submit(mk(), max_new_tokens=2)
    eng.step()
    # uncovered = 2 tokens each -> 2 + 2 <= 8: both admitted in one step
    assert len(eng.scheduler.running) + len([
        r for r in eng.scheduler.finished.values()
        if r.status == RequestStatus.DONE]) >= 3
    assert len(eng.scheduler.queue) == 0
    eng.run_until_idle()


# -- eviction-before-preemption ordering --------------------------------


def test_eviction_runs_before_preemption(model):
    """Growth pressure reclaims cold cached prefixes first; preemption
    only fires when nothing is reclaimable."""
    rng = np.random.default_rng(27)
    a = rng.integers(0, 255, (8,)).astype("int64")
    p1 = rng.integers(0, 255, (8,)).astype("int64")
    p2 = rng.integers(0, 255, (8,)).astype("int64")
    refs = [_ref_tokens(model, p, 12, block_size=4, max_seq_len=32)
            for p in (p1, p2)]
    before = _snap()
    # 10 usable blocks: a's 2 cached chunks + p1/p2 peaking at 5 each —
    # fits exactly IF the cold cache is evicted, with no preemption
    eng = ServingEngine(model, max_batch=2, block_size=4, max_seq_len=32,
                        num_blocks=11, temperature=0.0, background=False)
    eng.submit(a, max_new_tokens=4)
    eng.run_until_idle()
    assert eng.cache.num_cached_blocks() == 2
    h1 = eng.submit(p1, max_new_tokens=12)
    h2 = eng.submit(p2, max_new_tokens=12)
    eng.run_until_idle()
    after = _snap()
    assert h1.tokens() == refs[0] and h2.tokens() == refs[1]
    assert after["serving.prefix.evictions"] >= \
        before["serving.prefix.evictions"] + 2
    assert after["serving.preempt"] == before["serving.preempt"]


# -- oversubscription ----------------------------------------------------


def test_oversubscribed_mixed_shared_unique(model):
    """4x max_batch with a 50/50 mix of shared-prefix and unique
    prompts: every request reaches a terminal status and DONE outputs
    equal the uncontended references (preemption, re-prefill-with-hits,
    COW, and eviction all compose)."""
    rng = np.random.default_rng(28)
    system = rng.integers(0, 255, (16,)).astype("int64")
    prompts = []
    for i in range(8):
        if i % 2 == 0:
            prompts.append(np.concatenate(
                [system, rng.integers(0, 255, (2 + i,)).astype("int64")]))
        else:
            prompts.append(
                rng.integers(0, 255, (6 + i,)).astype("int64"))
    refs = [_ref_tokens(model, p, 6) for p in prompts]
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False)
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    handles[5].cancel()
    eng.run_until_idle()
    for i, h in enumerate(handles):
        assert h.status in RequestStatus.TERMINAL
        if i == 5:
            assert h.status == RequestStatus.CANCELLED
        else:
            assert h.status == RequestStatus.DONE
            assert h.tokens() == refs[i]
    assert eng.cache.num_free_blocks() == eng.cache.num_blocks - 1


# -- flag-off revert -----------------------------------------------------


def test_flag_off_reverts_to_private_blocks(model):
    """prefix_cache=False (the FLAGS_serving_prefix_cache=0 path): no
    planning, no registration, no deferred reclamation — and identical
    tokens."""
    rng = np.random.default_rng(29)
    system = rng.integers(0, 255, (24,)).astype("int64")
    prompts = [np.concatenate([system,
                               rng.integers(0, 255, (4,))
                               .astype("int64")]) for _ in range(3)]
    refs = [_ref_tokens(model, p, 6) for p in prompts]
    before = _snap()
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False,
                        prefix_cache=False)
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    after = _snap()
    for h, ref in zip(handles, refs):
        assert h.status == RequestStatus.DONE
        assert h.tokens() == ref
    for name in ("serving.prefix.hit_blocks", "serving.prefix.cow_copies",
                 "serving.prefix.evictions",
                 "serving.prefix.computed_tokens"):
        assert after[name] == before[name]
    assert eng.cache.num_cached_blocks() == 0
    assert len(eng.cache._free) == eng.cache.num_blocks - 1


def test_flag_default_routes_scheduler(model):
    """Scheduler reads FLAGS_serving_prefix_cache at construction."""
    flag = "FLAGS_serving_prefix_cache"
    orig = paddle.get_flags(flag)[flag]
    try:
        paddle.set_flags({flag: False})
        eng = ServingEngine(model, max_batch=1, block_size=8,
                            max_seq_len=32, temperature=0.0,
                            background=False)
        assert eng.scheduler.prefix_cache is False
        paddle.set_flags({flag: True})
        eng2 = ServingEngine(model, max_batch=1, block_size=8,
                             max_seq_len=32, temperature=0.0,
                             background=False)
        assert eng2.scheduler.prefix_cache is True
    finally:
        paddle.set_flags({flag: orig})


# -- the tail extend writes the donated pools in place (ISSUE 27) ----------

from conftest import (assert_lowered_donates, assert_pools_equal,  # noqa: E402
                      pools_numpy, undonated_twin)


def _shared_prefix_cache(model, kv_dtype, prompt):
    """A cache whose slot 0 prefilled ``prompt`` and registered it."""
    import jax.numpy as jnp

    cfg = model.config
    c = PagedKVCache(cfg.num_layers, cfg.num_kv_heads,
                     cfg.hidden_size // cfg.num_heads, num_blocks=17,
                     block_size=8, max_blocks_per_seq=8, max_batch=2,
                     dtype=jnp.float32, kv_dtype=kv_dtype)
    plan = c.plan_prefix(prompt)
    s0 = c.alloc_slot_cached(plan)
    model.paged_prefill(c, s0, prompt, temperature=0.0)
    c.commit_prefix(s0, plan)
    return c


@pytest.mark.filterwarnings("error::UserWarning")
@pytest.mark.parametrize("kv_dtype", [None, "int8"],
                         ids=["extend", "extend-int8"])
def test_extend_writes_the_donated_pools_like_the_eager_reference(
        model, kv_dtype):
    """A prompt that shares 16 of its 21 tokens extends a registered
    prefix: the program consumes the pools it is handed, agrees bitwise
    with its undonated twin, and what it wrote is the eager
    ``paged_prefill_write_masked`` of the tail's rows."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.paged import paged_prefill_write_masked
    from paddle_tpu.quantization import dequantize_rows

    rng = np.random.default_rng(27)
    first = rng.integers(3, 250, (20,)).astype("int64")
    second = np.concatenate([first[:16], rng.integers(3, 250, (5,))])
    cache = _shared_prefix_cache(model, kv_dtype, first)
    ref = _shared_prefix_cache(model, kv_dtype, first)
    assert_pools_equal(pools_numpy(cache), pools_numpy(ref))
    plan = cache.plan_prefix(second)
    assert (plan.tail_start, plan.write_start) == (16, 16)
    slot = cache.alloc_slot_cached(plan)
    assert ref.alloc_slot_cached(ref.plan_prefix(second)) == slot

    handed_in = cache.pool_arrays()
    tok = model.paged_prefill_extend(cache, slot, second, 16, 16,
                                     temperature=0.0)
    assert all(a.is_deleted() for a in handed_in)

    tail = np.zeros((1, 8), np.int64)
    tail[0, :5] = second[16:]
    row = jnp.asarray(ref.block_tables[slot])
    program, args = model.paged_call_args(
        ref, "extend",
        (jnp.asarray(tail), jnp.int32(16), jnp.int32(16), jnp.int32(21),
         row), (jax.random.key(0), jnp.float32(0.0)))
    old = ref.pool_arrays()
    try:
        assert_lowered_donates(program._jitted.lower(*args),
                               (6, 7, 8, 9) if kv_dtype else (6, 7))
        ref_tok, *new = undonated_twin(program)(*args)
    finally:
        model._param_rebind()(args[0])
    assert not any(a.is_deleted() for a in old)
    ref.rebind_pools(*new)
    assert tok == int(ref_tok)
    assert_pools_equal(pools_numpy(cache), pools_numpy(ref))

    # only positions 16..20 of the slot changed, and to these rows
    new = ref.pool_arrays()
    n = ref.num_layers
    pos = 16 + np.arange(8)
    blocks = ref.block_tables[slot][pos // 8]
    for i in range(n):
        if kv_dtype:
            k, v = (dequantize_rows(new[j][blocks, pos % 8],
                                    new[2 * n + j][blocks, pos % 8],
                                    jnp.float32) for j in (i, n + i))
            want = paged_prefill_write_masked(
                old[i], old[n + i], row, k, v, jnp.int32(16),
                jnp.int32(16), jnp.int32(21), k_scale=old[2 * n + i],
                v_scale=old[3 * n + i])
            got = (new[i], new[n + i], new[2 * n + i], new[3 * n + i])
        else:
            want = paged_prefill_write_masked(
                old[i], old[n + i], row, new[i][blocks, pos % 8],
                new[n + i][blocks, pos % 8], jnp.int32(16),
                jnp.int32(16), jnp.int32(21))
            got = (new[i], new[n + i])
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w)), i
