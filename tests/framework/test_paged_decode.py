"""Paged (block) KV-cache decode + continuous batching.

Mirrors the reference's block_multihead_attention tests
(test/legacy_test/test_block_multihead_attention.py: paged outputs pinned
to dense-cache outputs) plus cache-management unit tests.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.paged import (ContinuousBatchingEngine,
                                        PagedKVCache)
from paddle_tpu.models import Llama, LlamaConfig


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


def _dense_tokens(model, prompt, n):
    out = model.generate(paddle.to_tensor(prompt[None]), max_new_tokens=n,
                         temperature=0.0)
    return out.numpy()[0, len(prompt):].tolist()


def test_paged_equals_dense_greedy(model):
    prompt = np.random.default_rng(0).integers(0, 255, (12,)).astype(
        "int64")
    ref = _dense_tokens(model, prompt, 10)
    eng = ContinuousBatchingEngine(model, max_batch=2, block_size=8,
                                   max_seq_len=64, temperature=0.0)
    rid = eng.add_request(prompt, max_new_tokens=10)
    out = eng.run_to_completion()
    assert out[rid] == ref


def test_paged_crosses_block_boundaries(model):
    """Decode long enough to span several blocks (block_size=4)."""
    prompt = np.random.default_rng(1).integers(0, 255, (5,)).astype("int64")
    ref = _dense_tokens(model, prompt, 20)
    eng = ContinuousBatchingEngine(model, max_batch=1, block_size=4,
                                   max_seq_len=64, temperature=0.0)
    rid = eng.add_request(prompt, max_new_tokens=20)
    out = eng.run_to_completion()
    assert out[rid] == ref


def test_continuous_batching_staggered(model):
    """Requests admitted at different times must not perturb each other."""
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, 255, (9,)).astype("int64")
    p2 = rng.integers(0, 255, (6,)).astype("int64")
    p3 = rng.integers(0, 255, (14,)).astype("int64")
    refs = {i: _dense_tokens(model, p, n)
            for i, (p, n) in enumerate([(p1, 12), (p2, 8), (p3, 6)])}

    eng = ContinuousBatchingEngine(model, max_batch=2, block_size=8,
                                   max_seq_len=64, temperature=0.0)
    r1 = eng.add_request(p1, max_new_tokens=12)
    # a few steps with only request 1 live
    for _ in range(3):
        eng.step()
    r2 = eng.add_request(p2, max_new_tokens=8)
    for _ in range(2):
        eng.step()
    r3 = eng.add_request(p3, max_new_tokens=6)  # waits for a free slot
    out = eng.run_to_completion()
    assert out[r1] == refs[0]
    assert out[r2] == refs[1]
    assert out[r3] == refs[2]


def test_block_reuse_small_pool(model):
    """A pool sized for ~one sequence still serves many sequentially
    (finished sequences recycle their blocks)."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 255, (8,)).astype("int64") for _ in range(4)]
    refs = [_dense_tokens(model, p, 6) for p in prompts]
    eng = ContinuousBatchingEngine(model, max_batch=1, block_size=8,
                                   max_seq_len=16, num_blocks=3,
                                   temperature=0.0)
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    out = eng.run_to_completion()
    for rid, ref in zip(rids, refs):
        assert out[rid] == ref


def test_cache_alloc_free_cycle():
    c = PagedKVCache(1, 2, 16, num_blocks=8, block_size=4,
                     max_blocks_per_seq=4, max_batch=2)
    s0 = c.alloc_slot(10)  # 3 blocks
    s1 = c.alloc_slot(4)   # 1 block
    assert s0 is not None and s1 is not None and s0 != s1
    assert c.num_free_blocks() == 7 - 4  # 7 usable (block 0 reserved)
    assert c.alloc_slot(1) is None      # out of slots
    # growth
    assert c.ensure_capacity(s1, 5)     # needs a 2nd block
    assert c.num_free_blocks() == 2
    c.free_slot(s0)
    assert c.num_free_blocks() == 5
    s2 = c.alloc_slot(16)               # max_blocks_per_seq blocks
    assert s2 is not None
    # exhaustion: only 1 block left
    assert not c.ensure_capacity(s1, 12) or c.num_free_blocks() >= 0


def test_cache_rejects_oversize():
    c = PagedKVCache(1, 2, 16, num_blocks=8, block_size=4,
                     max_blocks_per_seq=2, max_batch=2)
    assert c.alloc_slot(100) is None  # > max_blocks_per_seq


def test_add_request_validates_inputs(model):
    eng = ContinuousBatchingEngine(model, max_batch=1, block_size=8,
                                   max_seq_len=32, temperature=0.0)
    with pytest.raises(ValueError):
        eng.add_request([])
    with pytest.raises(ValueError):
        eng.add_request(np.arange(40))                    # > max_seq_len
    with pytest.raises(ValueError):
        eng.add_request(np.arange(30), max_new_tokens=8)  # total too long
    with pytest.raises(ValueError):
        eng.add_request(np.arange(4), max_new_tokens=0)
    assert not eng.has_work
    # never-servable block demand rejected up front (was: infinite
    # admission loop in run_to_completion)
    tiny_pool = ContinuousBatchingEngine(model, max_batch=1, block_size=8,
                                         max_seq_len=32, num_blocks=3,
                                         temperature=0.0)
    with pytest.raises(ValueError):
        tiny_pool.add_request(np.arange(10), max_new_tokens=10)


def test_pool_exhaustion_preempts_not_truncates(model):
    """Pool exhaustion used to silently zero `_remaining` (truncating a
    running request); now the victim is preempted — blocks freed,
    requeued, re-prefilled — and still emits its FULL uncontended
    greedy output. serving.preempt counts the event."""
    from paddle_tpu.profiler import metrics

    rng = np.random.default_rng(7)
    p1 = rng.integers(0, 255, (8,)).astype("int64")
    p2 = rng.integers(0, 255, (8,)).astype("int64")
    refs = [_dense_tokens(model, p, 12) for p in (p1, p2)]
    before = metrics.snapshot("serving.")["serving.preempt"]
    # 7 usable blocks, each request peaks at 5 -> exhaustion mid-decode
    eng = ContinuousBatchingEngine(model, max_batch=2, block_size=4,
                                   max_seq_len=32, num_blocks=8,
                                   temperature=0.0)
    r1 = eng.add_request(p1, max_new_tokens=12)
    r2 = eng.add_request(p2, max_new_tokens=12)
    out = eng.run_to_completion()
    assert metrics.snapshot("serving.")["serving.preempt"] > before
    assert out[r1] == refs[0]        # full length, bit-identical
    assert out[r2] == refs[1]
    assert eng.cache.num_free_blocks() == eng.cache.num_blocks - 1


def test_paged_gqa_ratio(model):
    """tiny() config is GQA (4 q heads, 2 kv heads) — covered above — also
    check an MHA config decodes identically."""
    paddle.seed(1)
    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=4,
                      max_position_embeddings=32)
    m = Llama(cfg)
    m.eval()
    prompt = np.random.default_rng(5).integers(0, 127, (7,)).astype("int64")
    ref = _dense_tokens(m, prompt, 8)
    eng = ContinuousBatchingEngine(m, max_batch=2, block_size=4,
                                   max_seq_len=32, temperature=0.0)
    rid = eng.add_request(prompt, max_new_tokens=8)
    out = eng.run_to_completion()
    assert out[rid] == ref


# -- the pools are written in place (ISSUE 27) ----------------------------

from conftest import (assert_lowered_donates, assert_pools_equal,  # noqa: E402
                      dispatches, pools_numpy, undonated_twin)

# "Some donated buffers were not usable" is a UserWarning
_DONATION = pytest.mark.filterwarnings("error::UserWarning")
_KV = pytest.mark.parametrize("kv_dtype", [None, "int8"],
                              ids=["plain", "int8"])


def _cache(model, kv_dtype, max_batch=3):
    import jax.numpy as jnp

    cfg = model.config
    return PagedKVCache(cfg.num_layers, cfg.num_kv_heads,
                        cfg.hidden_size // cfg.num_heads, num_blocks=24,
                        block_size=8, max_blocks_per_seq=4,
                        max_batch=max_batch, dtype=jnp.float32,
                        kv_dtype=kv_dtype)


def _eager_prefill(model, cache, slot, prompt, spad):
    """The eager reference of a prefill: the plain forward's post-rope K
    and V of every layer, written by the eager ``paged_prefill_write``
    (an int8 cache's scales passed), one pool at a time as the serving
    path did before the write moved into the program. Returns the
    greedy first token."""
    import jax.numpy as jnp

    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.inference.paged import paged_prefill_write

    ids = np.zeros((1, spad), np.int64)
    ids[0, :len(prompt)] = prompt
    sink = []
    with no_grad():
        logits = model.forward(paddle.to_tensor(ids), kv_sink=sink)
    row = jnp.asarray(cache.block_tables[slot])
    for i, (k, v) in enumerate(sink):
        if cache.quantized:
            (cache.k_pools[i], cache.v_pools[i], cache.k_scales[i],
             cache.v_scales[i]) = paged_prefill_write(
                cache.k_pools[i], cache.v_pools[i], row, k._data[0],
                v._data[0], k_scale=cache.k_scales[i],
                v_scale=cache.v_scales[i])
        else:
            cache.k_pools[i], cache.v_pools[i] = paged_prefill_write(
                cache.k_pools[i], cache.v_pools[i], row, k._data[0],
                v._data[0])
    cache.seq_lens[slot] = len(prompt)
    return int(np.argmax(logits.numpy()[0, len(prompt) - 1]))


def _assert_decode_wrote_only_its_rows(old, new, cache, lens, active):
    """``new`` pools against the eager ``paged_decode_write`` of the rows
    the program wrote into the ``old`` ones: every other row of every
    pool is what it was."""
    import jax.numpy as jnp

    from paddle_tpu.inference.paged import paged_decode_write

    n = cache.num_layers
    slots = np.arange(cache.max_batch)
    blocks = cache.block_tables[slots, lens // cache.block_size]
    offs = lens % cache.block_size
    act = jnp.asarray(active)
    for i in range(n):
        if not cache.quantized:
            want = paged_decode_write(
                old[i], old[n + i], jnp.asarray(cache.block_tables),
                jnp.asarray(lens), new[i][blocks, offs],
                new[n + i][blocks, offs], act)
            got = (new[i], new[n + i])
        else:
            from paddle_tpu.quantization import dequantize_rows

            rows = [dequantize_rows(new[j][blocks, offs],
                                    new[2 * n + j][blocks, offs],
                                    jnp.float32)
                    for j in (i, n + i)]
            want = paged_decode_write(
                old[i], old[n + i], jnp.asarray(cache.block_tables),
                jnp.asarray(lens), rows[0], rows[1], act,
                k_scale=old[2 * n + i], v_scale=old[3 * n + i])
            got = (new[i], new[n + i], new[2 * n + i], new[3 * n + i])
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w)), i


@_DONATION
@_KV
def test_prefill_writes_the_donated_pools_like_the_eager_reference(
        model, kv_dtype):
    cache, ref = _cache(model, kv_dtype), _cache(model, kv_dtype)
    prompt = np.random.default_rng(5).integers(3, 250, (13,))
    slot = cache.alloc_slot(len(prompt))
    assert ref.alloc_slot(len(prompt)) == slot
    handed_in = cache.pool_arrays()
    tok = model.paged_prefill(cache, slot, prompt)
    assert all(a.is_deleted() for a in handed_in)
    assert not any(a.is_deleted() for a in cache.pool_arrays())
    assert tok == _eager_prefill(model, ref, slot, prompt, 16)
    assert_pools_equal(pools_numpy(cache), pools_numpy(ref), scale_ulp=1)
    assert cache.seq_lens[slot] == ref.seq_lens[slot] == len(prompt)


@_DONATION
@_KV
def test_decode_steps_write_the_donated_pools_and_nothing_else(
        model, kv_dtype):
    """Prefill two slots, then four decode steps: the donated program
    against its undonated twin (same function, fresh buffers) bitwise in
    tokens and pools, each step's input pools consumed, and each step's
    output the eager ``paged_decode_write`` of its new rows into its
    input."""
    import jax
    import jax.numpy as jnp

    cache, ref = _cache(model, kv_dtype), _cache(model, kv_dtype)
    rng = np.random.default_rng(6)
    last = np.zeros((cache.max_batch,), np.int64)
    for n in (13, 6):
        prompt = rng.integers(3, 250, (n,))
        slot = cache.alloc_slot(n + 4)
        assert ref.alloc_slot(n + 4) == slot
        last[slot] = model.paged_prefill(cache, slot, prompt)
        assert model.paged_prefill(ref, slot, prompt) == last[slot]
    active = np.array([True, True, False])
    ref_last = last.copy()
    for _ in range(4):
        handed_in = cache.pool_arrays()
        toks = np.asarray(model.paged_decode_step(cache, last, active))
        assert all(a.is_deleted() for a in handed_in)
        # the twin leaves ``ref``'s pools alive: old and new side by side
        old = ref.pool_arrays()
        lens = ref.seq_lens.copy()
        program, args = model.paged_call_args(
            ref, "decode", (jnp.asarray(ref_last, jnp.int32),),
            (ref.block_tables, jnp.asarray(lens), jnp.asarray(active),
             jax.random.key(0), jnp.float32(0.0)), mode="auto")
        try:
            ref_toks, *new = undonated_twin(program)(*args)
        finally:
            model._param_rebind()(args[0])
        assert not any(a.is_deleted() for a in old)
        ref.rebind_pools(*new)
        ref.seq_lens = np.where(active, lens + 1, lens).astype(np.int32)
        assert np.array_equal(toks[active], np.asarray(ref_toks)[active])
        assert_pools_equal(pools_numpy(cache), pools_numpy(ref))
        _assert_decode_wrote_only_its_rows(old, ref.pool_arrays(), ref,
                                           lens, active)
        last[active] = ref_last[active] = toks[active]


@_DONATION
@pytest.mark.parametrize("program", ["prefill", "prefill-int8", "decode",
                                     "decode-int8", "block-copy"])
def test_lowered_programs_mark_every_pool_as_donated(model, program):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference import paged

    quantized = program.endswith("int8")
    cache = _cache(model, "int8" if quantized else None)
    arrs = model._param_arrays()
    key, temp = jax.random.key(0), jnp.float32(0.0)
    try:
        if program.startswith("prefill"):
            jitted, args = model.paged_call_args(
                cache, "prefill",
                (jnp.zeros((1, 16), jnp.int64), jnp.int32(9),
                 jnp.asarray(cache.block_tables[0])), (key, temp))
            lowered = jitted._jitted.lower(*args)
            donated = (4, 5, 6, 7) if quantized else (4, 5)
        elif program.startswith("decode"):
            jitted, args = model.paged_call_args(
                cache, "decode", (jnp.zeros((3,), jnp.int32),),
                (cache.block_tables, jnp.asarray(cache.seq_lens),
                 jnp.ones((3,), bool), key, temp), mode="dense")
            lowered = jitted._jitted.lower(*args)
            donated = (2, 3, 4, 5) if quantized else (2, 3)
        else:
            lowered = paged._kv_block_copy.lower(
                cache.pool_arrays(), jnp.asarray([2], jnp.int32),
                jnp.asarray([1], jnp.int32))
            donated = (0,)
    finally:
        model._param_rebind()(arrs)
    assert_lowered_donates(lowered, donated)


@_DONATION
@_KV
def test_a_prefill_is_one_program_dispatch(model, kv_dtype):
    cache = _cache(model, kv_dtype)
    prompt = np.arange(3, 16)
    model.paged_prefill(cache, cache.alloc_slot(13), prompt)  # compiles
    slot = cache.alloc_slot(13)
    _tok, names = dispatches(
        lambda: model.paged_prefill(cache, slot, prompt))
    name = "llama_paged_prefill_q8" if kv_dtype else "llama_paged_prefill"
    assert names.count(name) == 1
    # what else runs is the key split and scalar conversions, never a
    # write (the 2 x num_layers eager scatters of old)
    assert not [n for n in names if "scatter" in n]
    assert len(names) <= 6, names


@_DONATION
@_KV
def test_copy_on_write_goes_through_the_donated_block_copy(model, kv_dtype):
    from paddle_tpu.profiler import metrics

    cache = _cache(model, kv_dtype)
    prompt = np.arange(3, 16)
    model.paged_prefill(cache, cache.alloc_slot(13), prompt)
    before = pools_numpy(cache)
    handed_in = cache.pool_arrays()
    c0 = metrics.snapshot("serving.kv.")
    cache._copy_block_rows(int(cache.block_tables[0, 0]), 20)
    c1 = metrics.snapshot("serving.kv.")
    assert all(a.is_deleted() for a in handed_in)
    src = int(cache.block_tables[0, 0])
    for old, new in zip(before, pools_numpy(cache)):
        want = old.copy()
        want[20] = old[src]
        assert np.array_equal(new, want)
    assert c1["serving.kv.donated_calls"] \
        == c0["serving.kv.donated_calls"] + 1
    assert c1["serving.kv.copied_calls"] == c0["serving.kv.copied_calls"]
