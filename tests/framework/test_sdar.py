"""SDAR (``models/sdar.py``): a sparse decoder served by diffusion over
blocks. At a tiny configuration (2 layers, 8 experts, 2 a token, heads of
32 on a hidden of 64, blocks of 4), seeded float32 weights, all on the
CPU with the Pallas kernels interpreted:

- the forward, and prefill + block steps + commits through ``Scheduler``,
  against the plain reference (``benchmarks/reference/
  sdar_moe_blockdiff.py``): logits first, then ids;
- a prefix hit and a preemption in the middle of a block give the tokens
  of an uncontended run;
- the dropless expert layer: the grouped matmul interpreted against the
  plain sorted product with uneven and empty groups, no row dropped at a
  load where the capacity path drops, the parts two halves of the
  experts give add up to the layer;
- a block of one row is ``paged_decode*``, a block of four the dense
  attention over ``len + 4`` keys;
- what the model is not served with raises one sentence at construction.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import moe
from paddle_tpu.inference import paged
from paddle_tpu.kernels.pallas import moe_gmm as K
from paddle_tpu.models import SDAR, Llama, LlamaConfig, SDARConfig
from paddle_tpu.models.sdar import block_causal_mask
from paddle_tpu.profiler import metrics, tracing
from paddle_tpu.serving import RequestStatus, ServingEngine
from paddle_tpu.serving.scheduler import Scheduler

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.reference import sdar_moe_blockdiff as R  # noqa: E402

PAD = 64  # one padded length, so that the reference compiles once


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = SDAR(SDARConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def ref(model):
    cfg = model.config
    fields = {"num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
              "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
              "rms_norm_eps": cfg.rms_norm_eps,
              "top_k": cfg.num_experts_per_tok,
              "norm_topk_prob": cfg.norm_topk_prob,
              "block_length": cfg.block_length}
    return R.weights_of(model), fields


def _generate(model, ref, prompt, n, on_forward=None):
    cfg = model.config
    return R.generate(*ref, prompt, n, denoise_steps=cfg.denoise_steps,
                      mask_token_id=cfg.mask_token_id, pad_to=PAD,
                      on_forward=on_forward)


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, size=n) for n in sizes]


# -- the model against the reference ---------------------------------------

def test_head_dim_is_the_configurations_own(model):
    attn = model.layers[0].self_attn
    assert attn.q_proj.weight.shape == [64, 4 * 32]  # not hidden x hidden
    assert attn.k_proj.weight.shape == [64, 2 * 32]
    assert attn.o_proj.weight.shape == [4 * 32, 64]
    assert model.layers[0].mlp.gate_proj.shape == [8, 64, 48]  # stacked
    assert model.tokens_per_block == 4


def test_forward_logits_equal_the_reference_under_the_block_causal_mask(
        model, ref):
    ids = _prompts(0, [37])[0]
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    want = np.asarray(R.logits(*ref, ids))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the mask is what makes it so: a causal forward differs inside blocks
    pos = jnp.arange(8)
    mask = np.asarray(block_causal_mask(pos, pos, 4))
    assert mask[0, 3] and not mask[3, 4] and mask[4, 0] and mask[5, 7]


@pytest.mark.parametrize("kernel", ["dense", "pallas"])
def test_scheduler_decodes_what_the_reference_generates(model, ref, kernel):
    """Prefill, denoising forwards and commits through the paged cache,
    every slot-forward recorded: its chosen tokens' logits are the
    reference's maxima of the same forward, then the ids are equal."""
    cases = [(21, 9), (3, 6), (32, 8), (17, 5), (40, 12)]
    prompts = _prompts(1, [n for n, _ in cases])
    sched = Scheduler(model, max_batch=4, block_size=16, max_seq_len=128,
                      dtype=jnp.float32, paged_kernel=kernel)
    records = []
    sched.block_observer = records.append
    reqs = [sched.submit(p, max_new_tokens=k)
            for p, (_, k) in zip(prompts, cases)]
    sched.run_to_completion()
    for req, prompt, (_, k) in zip(reqs, prompts, cases):
        forwards = []
        want = _generate(model, ref, prompt, k, on_forward=forwards.append)
        mine = [r for r in records if r["rid"] == req.rid]
        assert len(mine) == len(forwards)
        for got, fwd in zip(mine, forwards):
            assert got["seq_len"] == fwd["seq_len"]
            assert got["commit"] == fwd["commit"]
            assert list(got["ids"]) == fwd["ids"]
            assert (got["masked"] == fwd["masked"]).all()
            lg = fwd["logits"]
            np.testing.assert_allclose(got["logits"], lg.max(-1), atol=5e-6)
            assert (got["tokens"] == lg.argmax(-1)).all()
            prob = 1.0 / np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)
            np.testing.assert_allclose(got["probs"], prob, rtol=1e-4)
            # the step's load and each expert layer as the program ran it,
            # this slot's rows: the reference's router on that input
            assert 1 <= got["batch"] <= 4
            assert got["expert_rows"].shape == (2, 8)
            rows = got["moe_rows"]
            io, route = (np.asarray(a[:, :, rows]) for a in got["moe"])
            assert io.shape == (2, 2, 4, 64) and route.shape == (2, 2, 4, 2)
            for blk, (m, y), (w, e) in zip(
                    ref[0][1], io.swapaxes(0, 1), route.swapaxes(0, 1)):
                e = e.astype(np.int32)
                err, _ = R.compare_router(w, e, m, blk["router"], 2, True)
                assert err < 1e-6
                # and its experts on that input under that routing
                assert R.compare_experts(y, m, w, e, blk) < 1e-5
        assert req.status == RequestStatus.DONE
        assert req.generated == want and len(want) == k


def test_a_block_takes_its_denoising_steps_and_one_commit(model):
    before = metrics.snapshot("serving.")
    sched = Scheduler(model, max_batch=2, block_size=16, max_seq_len=64,
                      dtype=jnp.float32)
    # 8 and 11 prompt tokens: the second leaves 3 over, so its first
    # block opens with one masked position (one denoising forward)
    reqs = [sched.submit(p, max_new_tokens=8)
            for p in _prompts(2, [8, 11])]
    sched.run_to_completion()
    d = {k: v - before.get(k, 0) for k, v in
         metrics.snapshot("serving.").items() if not isinstance(v, dict)}
    assert [len(r.generated) for r in reqs] == [8, 8]
    # the accountant bills a forward by its positions and counts the
    # tokens a commit hands out, not one a forward
    assert [r.cost.tokens_emitted for r in reqs] == [8, 8]
    assert [r.cost.steps for r in reqs] == [6, 8]  # one a forward
    # request 1: 2 blocks x (2 + 1); request 2: (1 + 1) + 2 x (2 + 1),
    # the last block's tokens past max_new_tokens dropped
    assert d["serving.blockdiff.blocks_committed"] == 2 + 3
    assert d["serving.blockdiff.denoise_forwards"] == 4 + 5
    assert d["serving.blockdiff.commit_forwards"] == 5
    assert d["serving.blockdiff.tokens_unmasked"] == 8 + 1 + 8
    assert d["serving.decoded_tokens"] == 16
    forwards = 9 + 5
    cfg = model.config
    assert d["serving.moe.rows"] == forwards * 4 * cfg.num_layers \
        * cfg.num_experts_per_tok
    assert 0 < d["serving.moe.experts_hit"] <= d["serving.moe.rows"]
    assert d["serving.moe.max_rows"] >= d["serving.moe.rows"] \
        / cfg.num_experts
    hist = metrics.snapshot("serving.phase.")
    for name in tracing.BLOCK_PHASE_NAMES:
        assert hist[tracing.phase_histogram_name(name)]["count"] > 0
        assert name not in tracing.PHASE_NAMES


def test_a_prefix_hit_gives_the_same_tokens(model, ref):
    system = _prompts(3, [32])[0]  # two whole pages of 16
    prompts = [np.concatenate([system, t]) for t in _prompts(4, [5, 9, 2])]
    before = metrics.snapshot("serving.prefix.")
    eng = ServingEngine(model, max_batch=2, block_size=16, max_seq_len=128,
                        temperature=0.0, background=False,
                        dtype=jnp.float32)
    handles = []
    for p in prompts:  # one after the other, so that the pages are there
        handles.append(eng.submit(p, max_new_tokens=7))
        eng.run_until_idle()
    after = metrics.snapshot("serving.prefix.")
    assert after["serving.prefix.hit_blocks"] \
        - before["serving.prefix.hit_blocks"] >= 4
    for h, p in zip(handles, prompts):
        assert h.status == RequestStatus.DONE
        assert h.tokens() == _generate(model, ref, p, 7)


def test_a_preemption_in_the_middle_of_a_block_gives_the_same_tokens(
        model, ref):
    """Pool exhaustion preempts a request whose open block is partly
    unmasked: it forgets the block, re-prefills prompt + committed
    tokens through the extend or the plain program and goes on as an
    uncontended run does."""
    # the second prompt leaves 3 tokens over, so its first block takes a
    # forward less and it stays a step ahead: when the first request's
    # next block wants a page the 6 usable ones are taken, and the
    # newest request, the victim, is between two denoising forwards
    prompts = _prompts(5, [8, 7])
    eng = ServingEngine(model, max_batch=2, block_size=4, max_seq_len=32,
                        num_blocks=7, temperature=0.0, background=False,
                        dtype=jnp.float32)
    sched = eng.scheduler
    victims = []
    preempt = sched._preempt

    def watched(slot):
        victims.append((int(sched._blk_denoised[slot]),
                        bool(sched._blk_masked[slot].any())))
        preempt(slot)
    sched._preempt = watched
    handles = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run_until_idle()
    assert victims, "the pool was never exhausted"
    assert any(denoised and masked for denoised, masked in victims)
    for h, p in zip(handles, prompts):
        assert h.status == RequestStatus.DONE
        assert h.tokens() == _generate(model, ref, p, 12)
    assert eng.cache.num_free_blocks() == eng.cache.num_blocks - 1


def test_a_prefill_is_handed_a_copy_of_the_slots_table_row(model):
    """Nobody waits for a prefill that samples nothing, and the CPU
    backend reads a host array after the call returned: the row the
    program gets must not be the row the scheduler writes next."""
    eng = ServingEngine(model, max_batch=2, block_size=4, max_seq_len=32,
                        temperature=0.0, background=False,
                        dtype=jnp.float32)
    cache = eng.cache
    cache.block_tables[1, :3] = [5, 6, 7]
    row = SDAR._table_row(cache, 1)
    cache.block_tables[1] = 0  # the slot is preempted, its row zeroed
    assert np.asarray(row)[:4].tolist() == [5, 6, 7, 0]
    eng.close()


@pytest.mark.parametrize("seed", [6, 9])
def test_shared_prefixes_under_pool_pressure_give_the_uncontended_tokens(
        model, seed):
    """Requests that share page-aligned prefixes arrive a few a step on a
    pool of 16 pages for 4 slots: prefix hits, evictions and dozens of
    preemptions, some right behind the victim's own unwaited prefill.
    Every request ends with the tokens of a run on its own. (With a view
    of the table row handed to the prefill programs, a third of such
    runs gave some request other tokens.)"""
    rng = np.random.default_rng(seed)
    vocab = model.config.vocab_size

    def engine(**kw):
        return ServingEngine(model, max_batch=4, block_size=4,
                             max_seq_len=64, temperature=0.0,
                             background=False, dtype=jnp.float32,
                             admission=False, brownout=False, **kw)
    eng = engine(num_blocks=17, prefix_cache=True)
    shared = rng.integers(3, vocab, size=12)
    sent, pending = [], 24
    while pending or eng.has_work:
        for _ in range(min(int(rng.integers(0, 3)), pending)):
            prompt = rng.integers(3, vocab, size=int(rng.integers(4, 30)))
            if rng.random() < 0.3:
                k = min(prompt.size - 1, int(rng.integers(4, 13)))
                prompt[:k] = shared[:k]
            n = int(rng.integers(1, 20))
            sent.append((eng.submit(prompt, max_new_tokens=n), prompt, n))
            pending -= 1
        eng.step()
    assert sum(h.preempts for h, _, _ in sent) > 5
    alone = engine(prefix_cache=False)
    for h, prompt, n in sent:
        assert h.status == RequestStatus.DONE
        mine = alone.submit(prompt, max_new_tokens=n)
        alone.run_until_idle()
        assert h.tokens() == mine.tokens(), (len(prompt), n, h.preempts)
    eng.close()
    alone.close()


# -- what it is not served with ---------------------------------------------

@pytest.mark.parametrize("kwargs, sentence", [
    ({"kv_cache_dtype": "int8"}, "int8 KV"),
    ({"spec": True}, "speculation"),
    ({"mesh": "1x2"}, "serving mesh"),
    ({"role": "prefill"}, "disaggregated"),
    ({"temperature": 0.7}, "greedy"),
    ({"block_size": 6}, "multiples of the model's block"),
], ids=["int8-kv", "speculation", "mesh", "disaggregated", "sampling",
        "page-not-a-multiple"])
def test_what_a_block_diffusion_model_is_not_served_with_raises(
        model, kwargs, sentence):
    args = dict(max_batch=2, block_size=16, max_seq_len=64,
                temperature=0.0, background=False)
    args.update(kwargs)
    with pytest.raises(ValueError, match=sentence):
        ServingEngine(model, **args)
    assert model.serving_mesh() is None


def test_a_llama_whose_head_is_not_hidden_over_heads_serves():
    paddle.seed(1)
    cfg = LlamaConfig.tiny()
    cfg.head_dim = 2 * cfg.hidden_size // cfg.num_heads
    m = Llama(cfg)
    m.eval()
    assert m.layers[0].self_attn.q_proj.weight.shape == \
        [cfg.hidden_size, cfg.num_heads * cfg.head_dim]
    eng = ServingEngine(m, max_batch=2, block_size=8, max_seq_len=64,
                        temperature=0.0, background=False)
    prompt = _prompts(6, [9])[0]
    h = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_idle()
    toks = h.tokens()
    ids = np.concatenate([prompt, toks])
    with paddle.no_grad():
        logits = np.asarray(m(paddle.to_tensor(ids[None]))._data)[0]
    assert logits[len(prompt) - 1:-1].argmax(-1).tolist() == toks


@pytest.mark.parametrize("which, kwargs, want", [
    ("llama", {}, {("prefill", False, None), ("decode", False, "auto")}),
    ("llama", {"kv_cache_dtype": "int8", "spec": True},
     {("prefill", True, None), ("decode", True, "auto"),
      ("spec", True, None)}),
    ("sdar", {"paged_kernel": "pallas"},
     {("prefill", False, "pallas"), ("block_step", False, "pallas")}),
], ids=["llama", "llama-int8-spec", "sdar"])
def test_warmup_leaves_one_program_a_job_in_the_models_one_dict(
        model, which, kwargs, want):
    """A model holds its serving programs in ``paged_programs``, keyed
    (job, quantized, kernel mode): ``warmup()`` builds one a job the
    engine will run, whatever the number of buckets, and a second
    ``warmup()`` builds none."""
    if which == "llama":
        paddle.seed(2)
        model = Llama(LlamaConfig.tiny())
        model.eval()
    before = dict(model.paged_programs)
    eng = ServingEngine(model, max_batch=2, block_size=8, max_seq_len=32,
                        bucket_cap=32, temperature=0.0, background=False,
                        dtype=jnp.float32, **kwargs)
    n = eng.warmup()
    assert set(model.paged_programs) - set(before) == want - set(before)
    assert want <= set(model.paged_programs)
    built = dict(model.paged_programs)
    assert eng.warmup() == n
    assert model.paged_programs == built  # the same program objects
    eng.close()


# -- the dropless expert layer ----------------------------------------------

def _experts(rng, e=8, d=64, f=48):
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)
    return w(d, e) * 10, w(e, d, f), w(e, d, f), w(e, f, d)


def _dense_layer(x, router, wg, wu, wd, k):
    w, idx = moe.route_topk(x, router, k)
    t, e = x.shape[0], router.shape[1]
    full = jnp.zeros((t, e)).at[jnp.arange(t)[:, None], idx].set(w)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, wg)) \
        * jnp.einsum("td,edf->tef", x, wu)
    return jnp.einsum("tef,efd,te->td", h, wd, full)


@pytest.mark.parametrize("sizes", [
    [0, 5, 33, 0, 1, 16, 0, 9], [0, 0, 0, 64, 0, 0, 0, 0],
    [1, 1, 1, 1, 1, 1, 1, 1], [0, 0, 0, 0, 0, 0, 0, 3]],
    ids=["uneven", "one-expert", "one-row-each", "last-only"])
def test_moe_gmm_interpreted_is_the_plain_sorted_product(sizes):
    rng = np.random.default_rng(7)
    tm, e, k, n = 16, len(sizes), 64, 48
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = int(sizes.sum())
    m, offsets, padded, tile_expert, num_tiles = K.tile_layout(
        sizes, tm, rows)
    assert m % tm == 0 and int(num_tiles[0]) * tm == int(padded.sum())
    live = int(padded.sum())
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(e, k, n)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(e, k, n)) * 0.1, jnp.float32)
    got = K.moe_gmm(x, w, tile_expert, num_tiles, tm=tm, interpret=True)
    np.testing.assert_allclose(got[:live], K.moe_gmm_plain(x, w, padded)
                               [:live], atol=1e-5)
    got = K.moe_gmm_swiglu(x, w, w2, tile_expert, num_tiles, tm=tm,
                           interpret=True)
    np.testing.assert_allclose(
        got[:live], K.moe_gmm_swiglu_plain(x, w, w2, padded)[:live],
        atol=1e-5)
    # and group by group, by hand
    for g in range(e):
        lo, n_rows = int(offsets[g]), int(sizes[g])
        np.testing.assert_allclose(
            K.moe_gmm(x, w, tile_expert, num_tiles, tm=tm,
                      interpret=True)[lo:lo + n_rows],
            x[lo:lo + n_rows] @ w[g], atol=1e-5)


def test_tile_rows_follows_the_mean_group():
    assert K.tile_rows(2048, 128) == 16      # the block step: 16 a group
    assert K.tile_rows(8 * 2048, 128) == 64  # a 2048-token prefill: 128
    assert K.tile_rows(10 ** 6, 8) == 128 and K.tile_rows(3, 8) == 16


@pytest.mark.parametrize("route", ["plain", "interpret"])
def test_no_row_is_dropped_where_the_capacity_path_drops(route):
    """Every token prefers the same two experts: the capacity path keeps
    ``capacity`` rows an expert and zeroes the rest; the dropless path
    computes all of them."""
    rng = np.random.default_rng(8)
    t, k = 40, 2
    router, wg, wu, wd = _experts(rng)
    router = router.at[:, 2].set(0.0).at[:, 5].set(0.0)
    x = jnp.asarray(np.abs(rng.normal(size=(t, 64))), jnp.float32)
    router = router.at[:, 2].add(3.0).at[:, 5].add(2.0)  # x > 0: they win
    capacity = moe.TopKGate(64, 8, top_k=k).capacity(t)
    assert capacity < t
    _, combine, _ = moe.topk_gating(
        x @ router, k, capacity)
    kept = np.asarray(combine.sum((1, 2)))
    assert (kept < 0.999).sum() >= t - capacity  # the capacity path drops
    y, counts, _ = moe.dropless_moe(x, router, wg, wu, wd, top_k=k,
                                    route=route)
    assert counts.tolist() == [0, 0, t, 0, 0, t, 0, 0]
    want = _dense_layer(x, router, wg, wu, wd, k)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert (np.abs(np.asarray(y)).max(-1) > 0).all()


@pytest.mark.parametrize("route", ["plain", "interpret"])
def test_two_halves_of_the_experts_add_up_to_the_layer(route):
    rng = np.random.default_rng(9)
    t, k = 37, 2
    router, wg, wu, wd = _experts(rng)
    x = jnp.asarray(rng.normal(size=(t, 64)), jnp.float32)
    want = _dense_layer(x, router, wg, wu, wd, k)
    whole, counts, (weights, experts) = moe.dropless_moe(
        x, router, wg, wu, wd, top_k=k, route=route)
    np.testing.assert_allclose(whole, want, atol=1e-5)
    assert int(counts.sum()) == t * k
    # the router's own output rides along: what the benchmark holds to
    # the reference's float32 router
    assert weights.shape == experts.shape == (t, k)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    valid = jnp.arange(t) % 3 != 0  # a batch's idle slots route nowhere
    part, some, _ = moe.dropless_moe(x, router, wg, wu, wd, top_k=k,
                                     route=route, valid=valid)
    assert int(some.sum()) == int(valid.sum()) * k
    np.testing.assert_allclose(part, jnp.where(valid[:, None], want, 0.0),
                               atol=1e-5)
    lo, lo_counts, _ = moe.dropless_moe(
        x, router, wg[:4], wu[:4], wd[:4], top_k=k, expert_lo=0,
        route=route)
    hi, _, _ = moe.dropless_moe(x, router, wg[4:], wu[4:], wd[4:], top_k=k,
                                expert_lo=4, route=route)
    np.testing.assert_allclose(lo + hi, want, atol=1e-5)
    assert lo_counts.tolist() == counts.tolist()  # the router sees all


def test_the_layer_holds_a_range_of_stacked_experts():
    paddle.seed(2)
    layer = moe.DroplessMoE(64, 48, 8, 2, expert_range=(4, 8))
    assert layer.gate_proj.shape == [4, 64, 48]
    assert layer.router.shape == [64, 8]
    sink = []
    x = paddle.to_tensor(np.random.default_rng(3).normal(
        size=(2, 5, 64)).astype("float32"))
    y = layer(x, counts_sink=sink)
    assert y.shape == [2, 5, 64] and int(sink[0]._data.sum()) == 20
    with pytest.raises(ValueError, match="expert_range"):
        moe.DroplessMoE(64, 48, 8, 2, expert_range=(4, 9))


# -- attention of a block of rows ---------------------------------------------

def _pools(rng, nb=9, bs=8, hk=2, d=32):
    k = jnp.asarray(rng.normal(size=(nb, bs, hk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(nb, bs, hk, d)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0]],
                         jnp.int32)
    return k, v, tables


def test_a_block_of_one_row_is_the_decode_kernel():
    rng = np.random.default_rng(10)
    k, v, tables = _pools(rng)
    lens = jnp.asarray([13, 32, 0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, 4, 32)), jnp.float32)
    for mode in ("pallas", "dense"):
        one = paged.paged_block_attention(q[:, None], k, v, tables, lens,
                                          kernel_mode=mode)
        want = paged.paged_decode_attention(q, k, v, tables, lens,
                                            kernel_mode=mode)
        assert one.shape == (3, 1, 4, 32)
        assert (np.asarray(one[:, 0]) == np.asarray(want)).all()


@pytest.mark.parametrize("mode", ["pallas", "dense"])
def test_a_block_of_rows_sees_the_cache_and_each_other(mode):
    rng = np.random.default_rng(11)
    k, v, tables = _pools(rng)
    lens = jnp.asarray([13, 32, 0], jnp.int32)  # the block's own 4 in
    q = jnp.asarray(rng.normal(size=(3, 4, 4, 32)), jnp.float32)
    got = np.asarray(paged.paged_block_attention(
        q, k, v, tables, lens, kernel_mode=mode))
    for b in (0, 1):
        n = int(lens[b])
        keys = np.asarray(k[tables[b]]).reshape(-1, 2, 32)[:n]
        vals = np.asarray(v[tables[b]]).reshape(-1, 2, 32)[:n]
        for h in range(4):
            s = np.asarray(q[b, :, h]) @ keys[:, h // 2].T / np.sqrt(32)
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ vals[:, h // 2]
            np.testing.assert_allclose(got[b, :, h], want, atol=2e-5)
