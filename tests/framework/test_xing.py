"""Xing (models/xing.py): latent attention over a cache of one row a token
a layer, sigmoid-routed experts beside a shared expert, residual streams
mixed by hyper-connections, served through ``ServingEngine``.

Everything here is held to ``benchmarks/reference/latent_moe_hc.py``,
the plain float32 full forward (expanded attention, no cache, no
kernel), at ``XingConfig.tiny()``: 1 dense + 2 sparse layers, 4 heads
over a latent of 32 + 8 rotary values, 8 experts (2 a token), 4 streams.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from benchmarks.reference import latent_moe_hc as reference
from paddle_tpu.inference.paged import LatentRowSpec, PagedKVCache
from paddle_tpu.models import Xing, XingConfig
from paddle_tpu.serving import Scheduler, ServingEngine

# float32 on both sides; the program's sums run in another order
TOL = 2e-4


def _hf(cfg):
    """The configuration file's keys of a ``XingConfig``."""
    return {"num_attention_heads": cfg.num_heads,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "rms_norm_eps": cfg.rms_norm_eps, "hc_eps": cfg.hc_eps,
            "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters,
            "mhc_h_res_clamp_min": cfg.mhc_h_res_clamp_min,
            "mhc_h_res_clamp_max": cfg.mhc_h_res_clamp_max,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "rope_scaling": cfg.rope_scaling, "rope_theta": cfg.rope_theta}


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    m = Xing(XingConfig.tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def weights(model):
    return reference.weights_of(model)


@pytest.fixture(scope="module")
def fields(model):
    return reference.fields_of(_hf(model.config))


def _cache(model, slots=3, block=8, pages=8):
    cfg = model.config
    return PagedKVCache(
        model.kv_cache_layers, cfg.num_kv_heads, cfg.head_dim,
        num_blocks=slots * pages + 1, block_size=block,
        max_blocks_per_seq=pages, max_batch=slots, dtype=jnp.float32,
        latent_rows=model.latent_rows)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(3, 256, size=n)


def _deficit(ref_logits, toks):
    return reference.margin_check(ref_logits, 1, toks)[0]


def _held(cache, slot, upto, rope):
    with cache.pool_lock:
        row = cache.block_tables[slot]
        return [(np.array(kp[row]).reshape(-1, kp.shape[-1])[:upto],
                 np.array(vp[row]).reshape(-1, vp.shape[-1])[:upto, :rope])
                for kp, vp in zip(cache.k_pools, cache.v_pools)]


def _engine(model, **kw):
    args = dict(temperature=0.0, dtype=jnp.float32, max_batch=3,
                block_size=8, max_seq_len=64, bucket_cap=64,
                paged_kernel="pallas")
    args.update(kw)
    return ServingEngine(model, **args)


def _served_right(weights, fields, prompt, toks):
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int64)
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(toks))
    want, _ = reference.forward(weights, fields, seq, rows)
    return _deficit(want, [int(t) for t in toks]) < TOL


# -- the configuration -------------------------------------------------------------

def test_the_published_configuration_and_what_is_refused():
    cfg = XingConfig()
    assert (cfg.head_dim, cfg.kv_lora_rank, cfg.q_lora_rank) == (192, 512,
                                                                 768)
    assert abs(cfg.softmax_scale - 0.14468) < 1e-5
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        XingConfig(num_nextn_predict_layers=1)
    with pytest.raises(ValueError, match="n_group"):
        XingConfig(n_group=4)
    with pytest.raises(ValueError, match="sigmoid"):
        XingConfig(scoring_func="softmax")


def test_yarn_frequencies_blend_between_the_two_bounds():
    from paddle_tpu.models.xing import yarn_inv_freq
    cfg = XingConfig()
    got = yarn_inv_freq(64, cfg.rope_theta, cfg.rope_scaling)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(got[-8:], plain[-8:] / 64, rtol=1e-6)
    assert (np.diff(got) < 0).all()
    np.testing.assert_allclose(
        got, reference.yarn_inv_freq(64, 10000.0, cfg.rope_scaling),
        rtol=1e-6)


def test_the_model_declares_its_cache_and_its_initialisation(model):
    assert model.latent_rows == LatentRowSpec(latent=32, rope=8)
    # the rotary keys in whole 128-lane tiles behind the compressed
    # part: the lanes the two pools of PR 34 held, in one row
    assert model.latent_rows.lanes == 32 + 128
    assert LatentRowSpec(latent=512, rope=64).lanes == 640
    assert model.kv_cache_layers == 3 and model.recurrent_state is None
    assert model.decode_extras == 2 * 8 and model.decode_tap
    hc = model.layers[1].hc_mlp
    np.testing.assert_allclose(np.asarray(hc.alpha._data), 0.25)
    bias = np.asarray(hc.bias._data)
    np.testing.assert_array_equal(bias[:8], 0.0)
    np.testing.assert_allclose(bias[8:].reshape(4, 4), 2.0 * np.eye(4))
    router_bias = np.asarray(
        model.layers[1].mlp.e_score_correction_bias._data)
    assert 0.02 < router_bias.std() < 0.3   # seeded, and not zero
    assert not hasattr(model.layers[0].mlp, "router")  # layer 0 is dense


# -- the forward against the reference ----------------------------------------------

def test_forward_is_the_references(model, weights, fields):
    ids = _ids(40)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    want, _ = reference.forward(weights, fields, ids, np.arange(40))
    np.testing.assert_allclose(got, want, atol=TOL * np.abs(want).max())


def test_the_stream_mix_is_doubly_stochastic(model, weights, fields):
    x = np.random.default_rng(1).normal(size=(6, 4, 32)).astype(np.float32)
    for w in (weights["layers"][0]["hc_attn"],
              weights["layers"][2]["hc_mlp"]):
        pre, post, m = (np.asarray(a) for a in reference.stream_maps(
            jnp.asarray(x), w, fields))
        assert np.abs(m.sum(-1) - 1).max() < 1e-4
        assert np.abs(m.sum(-2) - 1).max() < 1e-4
        assert (m > 0).all() and (0 < pre).all() and (pre < 1).all()
        assert (0 < post).all() and (post < 2).all()
        # the token's own streams move the maps (a_* are not near zero)
        assert np.abs(pre - pre[0]).max() > 1e-3
        assert np.abs(m - m[0]).max() > 1e-3
    got = model.layers[0].hc_attn.maps(jnp.asarray(x))
    want = reference.stream_maps(jnp.asarray(x),
                                 weights["layers"][0]["hc_attn"], fields)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("mode", ["pallas", "dense"])
def test_prefill_extend_and_decode_through_the_latent_cache(
        model, weights, fields, mode):
    """paged_prefill (a padded bucket, expanded) -> paged_prefill_extend
    (expanded over cached rows) -> 24 decode steps (absorbed): every
    token is the reference's choice over the whole sequence, the cache
    holds the rows the full forward would, and every step replays."""
    cfg = model.config
    ids = _ids(45, seed=1)
    want, cached = reference.forward(weights, fields, ids, np.arange(45))
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
    taps = []
    for p in range(21, 45):
        last[slot] = ids[p]
        assert cache.ensure_capacity(slot, p + 1)
        out = np.asarray(model.paged_decode_step(
            cache, last, active, kernel_mode=mode,
            state_observer=lambda: (slot, taps)))
        assert out.shape == (3 + model.decode_extras,)
        rows = model.decode_expert_rows(out)
        assert rows.shape == (2, 8) and (rows.sum(axis=1) == 2).all()
        toks.append(int(out[slot]))
        at.append(p)
    assert _deficit(want[at], toks) < TOL
    held = _held(cache, slot, 45, cfg.qk_rope_head_dim)
    for got, ref in zip(held, cached):
        assert reference.rel_rms(np.concatenate(got, 1),
                                 np.concatenate(ref, 1)) < TOL
    assert len(taps) == 24 and [p for p, _ in taps] == list(range(21, 45))
    for pos, tap in (taps[0], taps[-1]):
        layers, tail = model.unpack_tap(tap)
        assert tail["active"][0] == 1.0
        assert reference.rel_rms(tail["logits"], want[pos]) < TOL
        reads = reference.replay_step(weights, fields, layers, pos, held)
        assert max(reads.values()) < TOL, reads


@pytest.fixture(scope="module")
def tapped(model):
    """A prefill and ten decode steps on the plain route: the last
    step's position and tap, and the rows the slot's cache holds."""
    ids = _ids(30, seed=2)
    cache = _cache(model)
    slot = cache.alloc_slot(20)
    model.paged_prefill(cache, slot, ids[:20], pad_to=32,
                        kernel_mode="dense")
    last = np.zeros((3,), np.int64)
    active = np.zeros((3,), bool)
    active[slot] = True
    taps = []
    for p in range(20, 30):
        last[slot] = ids[p]
        assert cache.ensure_capacity(slot, p + 1)
        model.paged_decode_step(cache, last, active, kernel_mode="dense",
                                state_observer=lambda: (slot, taps))
    pos, tap = taps[-1]
    return pos, model.unpack_tap(tap)[0], _held(
        cache, slot, 30, model.config.qk_rope_head_dim)


@pytest.mark.parametrize("fault, reading", [
    ("int8_rows", "row"), ("bf16_router", "router"), ("sinkhorn_5", "mix"),
    ("no_shared", "experts"), ("no_bias", "router"),
    ("no_yarn_scale", "attn"), ("other_head", "attn"),
    ("other_maps", "mix")])
def test_each_planted_fault_fails_its_tolerance(weights, fields, tapped,
                                                fault, reading):
    """The reference made to get one thing wrong reads past the
    tolerance the program holds, in the reading that is there for it
    (that each moves the full forward's logits too:
    tests/benchmark_harness/test_latent_cell.py)."""
    pos, layers, held = tapped
    right = reference.replay_step(weights, fields, layers, pos, held)
    assert max(right.values()) < 1e-6
    wrong = reference.replay_step(weights, fields, layers, pos, held,
                                  **reference.FAULTS[fault])
    assert wrong[reading] > 1e-4, wrong


def test_a_prefix_hit_equals_the_cold_prefill(model, weights, fields):
    """The second request with the same first two blocks maps them and
    computes only its tail through ``paged_prefill_extend``: its token
    and the rows it caches are the cold prefill's."""
    shared = _ids(16, seed=3)
    a = np.concatenate([shared, _ids(5, seed=4)])
    b = np.concatenate([shared, _ids(7, seed=5)])
    cache = _cache(model)
    plan = cache.plan_prefix(a)
    slot_a = cache.alloc_slot_cached(plan)
    model.paged_prefill(cache, slot_a, a, pad_to=32)
    cache.commit_prefix(slot_a, plan)
    plan = cache.plan_prefix(b)
    assert (plan.covered_tokens, plan.hit_blocks) == (16, 2)
    slot_b = cache.alloc_slot_cached(plan)
    hit = model.paged_prefill_extend(cache, slot_b, b, plan.tail_start,
                                     plan.write_start, pad_to=8)
    cache.commit_prefix(slot_b, plan)
    assert cache.num_shared_blocks() == 2
    cold = _cache(model)
    slot_c = cold.alloc_slot(len(b))
    assert model.paged_prefill(cold, slot_c, b, pad_to=32) == hit
    rope = model.config.qk_rope_head_dim
    for got, want in zip(_held(cache, slot_b, len(b), rope),
                         _held(cold, slot_c, len(b), rope)):
        np.testing.assert_allclose(got[0], want[0], atol=1e-5)
        np.testing.assert_allclose(got[1], want[1], atol=1e-5)
    want, _ = reference.forward(weights, fields, b, [len(b) - 1])
    assert _deficit(want, [hit]) < TOL


# -- through the engine ---------------------------------------------------------------

def test_the_engine_is_token_identical_to_generate(model, weights, fields):
    """``ServingEngine`` (the run-ahead loop, the prefix cache on) gives
    the tokens the plain whole-forward greedy loop gives."""
    prompts = [_ids(n, seed=10 + n) for n in (9, 17, 12)]
    with _engine(model) as eng:
        assert eng.scheduler.prefix_cache
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        served = [list(h.result(timeout=300)) for h in handles]
        again = list(eng.submit(prompts[1], max_new_tokens=10)
                     .result(timeout=300))
    # the whole forward a token costs a compile a length on the CPU:
    # one prompt, its first tokens; the reference holds the rest
    assert served[0][:3] == model.generate(prompts[0][None],
                                           max_new_tokens=3)
    for prompt, toks in zip(prompts, served):
        assert _served_right(weights, fields, prompt, np.asarray(toks))
    assert again == served[1]            # a prefix hit changes nothing


def test_preempt_then_readmit_prefills_again(model, weights, fields):
    """A pool too small for two growing requests: one is preempted and
    prefilled again (prompt + what it generated); every token of both
    is still the reference's."""
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
        assert _served_right(weights, fields, prompt,
                             np.asarray(req.generated))


def test_the_token_path_counts_experts_and_runs_a_step_ahead(model, weights,
                                                             fields):
    """``serving.moe.*`` from the counts a plain decode step packs behind
    its tokens; the latent pool's bytes as a gauge; steps dispatched
    with the step before unread; pools donated."""
    from paddle_tpu.profiler import metrics

    before = metrics.snapshot("serving.")
    sched = Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                      dtype=jnp.float32, paged_kernel="pallas")
    cache = sched.cache
    assert cache.latent_spec == model.latent_rows
    # one pool a layer and nothing beside it
    assert [p.shape for p in cache.row_pools] == [(17, 8, 1, 160)] * 3
    assert cache.pool_lists() == (cache.row_pools, [], [], [])
    assert cache.pool_arrays() == cache.row_pools
    pools = 3 * 17 * 8 * (32 + 128) * 4         # as with two pools
    assert cache.pool_bytes() == pools
    assert metrics.snapshot("serving.mla.latent_bytes")[
        "serving.mla.latent_bytes"] == pools
    sched.submit(_ids(5, seed=10), max_new_tokens=3)   # warms the step
    sched.run_to_completion()
    handed = cache.row_pools[0]
    reqs = [sched.submit(_ids(n, seed=n), max_new_tokens=9)
            for n in (10, 7)]
    sched.run_to_completion()
    assert handed.is_deleted() and not cache.row_pools[0].is_deleted()
    delta = {k: v - before.get(k, 0) for k, v in
             metrics.snapshot("serving.").items()
             if isinstance(v, (int, float))}
    # 2 + 8 decode steps; the second batch's 8 have 2 live rows: 2
    # sparse layers x 2 experts a token
    assert delta["serving.moe.rows"] == 2 * 2 * (2 + 8 * 2)
    assert delta["serving.moe.experts_hit"] <= delta["serving.moe.rows"]
    assert delta["serving.moe.max_rows"] >= 2 * 10
    assert delta["serving.kv.copied_calls"] == 0
    assert delta["serving.decode.ahead"] >= 6
    assert delta["serving.kernel.mla_decode.pallas"] >= 3
    assert delta.get("serving.kernel.mla_decode.plain", 0) == 0
    for req in reqs:
        assert req.status == "DONE"
        assert _served_right(weights, fields, req.prompt,
                             np.asarray(req.generated))


def test_the_pair_of_names_reads_the_row_pool_without_making_a_pool(model):
    """``cache.k_pools[i][table]`` / ``cache.v_pools[i][table]`` of a
    latent cache answer as ``benchmarks/drivers/serve_latent._held_rows``
    asks them: the compressed part exactly ``latent`` wide, the lanes
    behind it at least ``rope`` wide, the values those written: a gather
    of the pages asked for and a lane slice, never an array a pool
    wide."""
    cache = _cache(model)
    spec = model.latent_rows
    slot = cache.alloc_slot(21)
    model.paged_prefill(cache, slot, _ids(21, seed=4), pad_to=32)
    assert len(cache.k_pools) == len(cache.v_pools) == cache.num_layers
    table = jnp.asarray(cache.block_tables[slot, :3].copy())
    for i in range(cache.num_layers):
        c = cache.k_pools[i][table]
        k_r = cache.v_pools[i][table]
        assert c.shape == (3, 8, 1, spec.latent)
        assert k_r.shape[:3] == (3, 8, 1) and k_r.shape[-1] >= spec.rope
        assert cache.k_pools[i].shape == (25, 8, 1, spec.latent)
        assert cache.v_pools[i].shape[-1] == spec.lanes - spec.latent
        for got in (c, k_r):     # pages, not pools
            assert got.size <= 3 * 8 * spec.lanes
        # what the prefill wrote (held to the reference by the tests
        # above, which read through these names)
        rows = np.asarray(cache.row_pools[i][table])
        np.testing.assert_array_equal(c, rows[..., :spec.latent])
        np.testing.assert_array_equal(k_r, rows[..., spec.latent:])
        assert np.asarray(c).reshape(24, -1)[:21].any(axis=1).all()
        assert np.asarray(k_r)[..., :spec.rope].any()
        assert not np.asarray(k_r)[..., spec.rope:].any()
    # after a step the names read the pools the step returned
    handed = cache.row_pools[0]
    assert cache.ensure_capacity(slot, 22)
    model.paged_decode_step(cache, np.array([5, 0, 0]),
                            np.array([True, False, False]))
    assert handed.is_deleted()
    assert np.asarray(cache.k_pools[0][table]).shape == (3, 8, 1,
                                                         spec.latent)


def test_the_row_pool_holds_the_bytes_the_two_pools_held():
    """At the published widths (512 + 64) a row is 640 lanes: the 512 +
    128 of PR 34's two pools, in one. ``pool_bytes()`` does not move."""
    spec = LatentRowSpec(latent=512, rope=64)
    cache = PagedKVCache(2, 1, 576, num_blocks=3, block_size=16,
                         max_blocks_per_seq=2, max_batch=1,
                         dtype=jnp.bfloat16, latent_rows=spec)
    assert [p.shape for p in cache.pool_arrays()] == [(3, 16, 1, 640)] * 2
    assert cache.pool_bytes() == 2 * 3 * 16 * (512 + 128) * 2
    assert cache.head_dim == 512 and cache.num_kv_heads == 1


# -- what such a cache cannot do yet ---------------------------------------------------

def test_what_a_latent_cache_cannot_do_is_refused_with_the_reason(model):
    from paddle_tpu.serving import kv_transfer
    from paddle_tpu.serving.mesh import ServingMesh
    from paddle_tpu.serving.scheduler import HandoffError

    with pytest.raises(ValueError, match="latent"):
        Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                  dtype=jnp.float32, spec=True)
    with pytest.raises(ValueError, match="serving mesh"):
        Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                  dtype=jnp.float32, mesh=ServingMesh(1, 2))
    with pytest.raises(ValueError, match="int8 KV"):
        Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                  dtype=jnp.float32, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="one device"):
        PagedKVCache(3, 4, 24, num_blocks=9, block_size=8,
                     max_blocks_per_seq=4, max_batch=2, num_slices=2,
                     latent_rows=model.latent_rows)
    sched = Scheduler(model, max_batch=2, block_size=8, max_seq_len=64,
                      dtype=jnp.float32)
    with pytest.raises(ValueError, match="latent rows"):
        sched.submit(_ids(9), max_new_tokens=4, prefill_only=True)
    with pytest.raises(ValueError, match="latent rows"):
        kv_transfer.export_prefix(sched.cache, _ids(16))
    with pytest.raises(ValueError, match="latent rows"):
        kv_transfer.import_prefix(sched.cache, b"")
    with pytest.raises(HandoffError, match="latent"):
        sched.admit_handoff(_ids(9), 5)
    plain = PagedKVCache(3, 4, 24, num_blocks=9, block_size=8,
                         max_blocks_per_seq=4, max_batch=2,
                         dtype=jnp.float32)
    with pytest.raises(ValueError, match="not built for this model"):
        model.paged_decode_step(plain, np.zeros(2, np.int64),
                                np.ones(2, bool))
