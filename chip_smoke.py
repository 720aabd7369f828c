#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

    python3 chip_smoke.py

Drives the two main paths once through the entry points a user calls, at
the full width of models the repo supports, with seeded random weights:

- train:   GPT-2 345M (``GPTConfig.gpt2_medium()``, every layer) in bf16
           through ``paddle.jit.TrainStep`` with AdamW + global-norm clip,
           batch 8 x 1024, a few steps on one fixed batch;
- serve:   a Llama at ``LlamaConfig.llama3_8b()`` widths with the depth
           cut to ``SERVE_LAYERS`` through ``ServingEngine`` (background
           driver, FLAGS_paged_kernel=auto, prefix cache on): ``warmup()``
           then streamed requests, once with a table long enough for the
           chunked decode kernel and once short enough for the per-page
           one;
- kernels: every kernel under ``paddle_tpu/kernels/pallas/`` compiled by
           Mosaic at those widths and compared with its dense reference;
- four chips (when the process sees >= 4 TPU devices): the same Llama
           through ``ServingEngine(mesh="1x4")`` and GPT-2 345M through a
           2x2 dp x tp ``ShardedTrainStep``, compared with the one-chip
           phases.

There is no CPU mode: the script exits 2 with one line unless
``jax.default_backend() == "tpu"``. One process holds the chip; nothing is
spawned. The phases are plain functions of a config so that
tests/framework/test_chip_smoke.py can call them at tiny widths on the CPU
with interpret-mode kernels. Each phase raises on its first failed check.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
What the other lines print (wall time split into XLA compile and the rest,
peak HBM, versions) are facts about this run, not metrics.
"""

from __future__ import annotations

import gc
import importlib.metadata
import json
import sys
import time

# Depth cut of the served model. At Llama-3-8B widths one layer is 218.1M
# parameters (436 MB in bf16) and embedding + head are 1,050.7M (2.10 GB),
# so 8 of the 32 layers are 2.80G parameters = 5.59 GB of weights. The
# default engine's pool (8 slots x 2048 tokens, block 16) adds 64 MiB a
# layer = 0.54 GB, held twice for a moment because the decode step does
# not donate it (ROADMAP S3), and the short-table engine's pool 0.13 GB.
# Deeper cuts fit the 16 GB too; 8 keeps the cold compile of the eight
# prefill buckets inside the 1200 s the smoke is allowed.
SERVE_LAYERS = 8

# |pallas - dense| <= tol * max|dense| for decode attention on bf16 pools:
# both routes round the probabilities to bf16 (relative 2^-9) before the
# PV matmul and the output to bf16 again, but normalise in a different
# order, so a few bf16 ulps of the output's scale is the honest bound;
# a wrong mask, scale or page would miss it by orders of magnitude.
BF16_TOL = 2.0 ** -6

# First-step logits of the same bf16 weights on one chip and over the 1x4
# mesh: tensor parallelism rounds each row-parallel partial sum to bf16
# before the all-reduce, so every layer re-orders its roundings. In
# float32 the two layouts agree to 1.5e-6; in bf16 they differ by 1.2e-2 of
# the logits' scale at hidden 1024 x 8 layers (CPU calibration) and by
# 2.5e-2 to 3.0e-2 at these widths on the chip (my runs, PR 21) — as far
# as either is from its own float32 result. A wrong head-to-shard map or a missing
# all-reduce is an error of the scale itself.
MESH_LOGITS_TOL = 2.0 ** -4


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def _require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _compile_stats():
    """(count, seconds) of XLA backend compiles so far in this process;
    a persistent-cache hit counts with the time it took to load."""
    from paddle_tpu.profiler import metrics

    snap = metrics.snapshot("xla.compile.")
    return snap["xla.compile.count"], snap["xla.compile.seconds"]["sum"]


def _hbm():
    """Device 0's bytes in use and peak so far (None where the backend
    reports no memory stats, as the CPU does)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {"hbm_bytes_in_use": stats.get("bytes_in_use"),
            "peak_hbm_bytes": stats.get("peak_bytes_in_use")}


def _require_on(platform, model, phase):
    where = {d.platform for p in model.parameters()
             for d in p._data.devices()}
    _require(where == {platform},
             f"{phase}: parameters live on {where}, expected {platform}")


def _ragged_lens(rng, slots, capacity):
    """int32 sequence lengths in [1, capacity], among them a full table
    and a single token."""
    import jax.numpy as jnp
    import numpy as np

    lens = rng.integers(1, capacity + 1, (slots,))
    lens[0], lens[-1] = capacity, 1
    return jnp.asarray(lens.astype(np.int32))


def _rel_err(got, ref):
    """max|got - ref| / max|ref|, in float32 on the host."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    _require(np.isfinite(got).all(), "non-finite values in kernel output")
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def _pallas_calls(lowered):
    """(kernel names in the lowered module, Mosaic custom calls left in
    the compiled executable). Interpret-mode kernels give ([], 0)."""
    import re

    names = sorted(set(re.findall(r'kernel_name = "(\w+)"',
                                  lowered.as_text())))
    return names, lowered.compile().as_text().count("tpu_custom_call")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _gpt_batch(config, batch, seq):
    import numpy as np

    import paddle_tpu as paddle

    return paddle.to_tensor(np.random.default_rng(0).integers(
        0, config.vocab_size, (batch, seq)).astype("int64"))


def _gpt_and_optimizer(config, dtype):
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.models import GPT

    paddle.seed(0)
    model = GPT(config)
    model.to(dtype=dtype)
    # constant 3e-4 with no warm-up overshot on the fourth step of the
    # fixed batch (loss 10.40 -> 11.94 -> 10.22, my chip run, PR 21)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    return model, opt


def _run_steps(step, ids, steps):
    """Losses of ``steps`` steps on ``ids`` and the number of XLA compiles
    after the second one (the first compiles the step, the second sees the
    donated buffers' final layouts; from then on nothing may compile)."""
    import numpy as np

    losses = []
    settled = None
    for i in range(steps):
        losses.append(float(np.asarray(step(ids).numpy())))
        if i == 1:
            settled = _compile_stats()[0]
    return losses, _compile_stats()[0] - settled


def train_phase(config, batch, seq, steps, *, dtype, platform,
                expect_kernels):
    """``steps`` (>= 3) TrainStep steps of ``GPT(config)`` on one fixed
    batch. ``platform`` is where the parameters must live;
    ``expect_kernels`` names the Pallas kernels the compiled step must
    hold (empty where they run interpreted)."""
    import numpy as np

    import paddle_tpu as paddle

    model, opt = _gpt_and_optimizer(config, dtype)
    _require_on(platform, model, "train")
    step = paddle.jit.TrainStep(model, opt, lambda m, ids: m.loss(ids, ids))
    ids = _gpt_batch(config, batch, seq)
    losses, recompiles = _run_steps(step, ids, steps)
    _require(np.isfinite(losses).all(), f"train: loss not finite: {losses}")
    _require(losses[-1] < losses[0], f"train: loss did not fall: {losses}")
    _require(recompiles == 0,
             f"train: {recompiles} XLA compiles after the second step")
    names, calls = _pallas_calls(step.lower(ids))
    _require(set(expect_kernels) <= set(names)
             and calls >= len(expect_kernels),
             f"train: compiled step holds kernels {names} ({calls} Mosaic "
             f"calls), expected {sorted(expect_kernels)}")
    return {"params": model.num_params(non_embedding=False),
            "losses": [round(v, 4) for v in losses],
            "kernels": names, "mosaic_calls": calls}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _build(cls, config, dtype):
    """Seeded ``cls(config)`` constructed directly in ``dtype`` (a
    float32 copy of the 8B widths is a transient the chip cannot hold);
    ``to`` then casts what a layer pinned to float32, the embedding and
    the norm weights."""
    import paddle_tpu as paddle

    paddle.seed(0)
    prev = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        model = cls(config)
    finally:
        paddle.set_default_dtype(prev)
    model.to(dtype=dtype)
    model.eval()
    return model


def _first_step_logits(model, prompt):
    """float32 logits of the position the first served token is drawn
    from, through the model's dense forward (the program the prefill
    jit traces)."""
    import numpy as np

    import paddle_tpu as paddle

    with paddle.no_grad():
        logits = model(paddle.to_tensor(np.asarray(prompt)[None, :]))
    return np.asarray(logits._data[0, -1].astype("float32"))


def _decode_attention_check(cache, num_heads, kernel_mode, dtype):
    """One decode-attention call on the engine's live layer-0 pools:
    the routed (Pallas) kernel against the dense reference."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.inference.paged import (paged_decode_attention,
                                            paged_decode_attention_dense)

    k_pool, v_pool = cache.k_pools[0], cache.v_pools[0]
    written = np.flatnonzero(np.asarray(
        jnp.any(k_pool != 0, axis=(1, 2, 3))))
    written = written[written != 0]  # block 0 is the null block
    _require(written.size > 0, "serve: no KV block was ever written")
    b, pages, bs = cache.max_batch, cache.max_blocks_per_seq, cache.block_size
    rng = np.random.default_rng(1)
    tables = jnp.asarray(np.resize(written, (b, pages)).astype(np.int32))
    lens = _ragged_lens(rng, b, pages * bs)
    q = jnp.asarray(rng.standard_normal((b, num_heads, cache.head_dim)),
                    dtype)
    got = paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                 kernel_mode=kernel_mode)
    ref = paged_decode_attention_dense(q, k_pool, v_pool, tables, lens)
    return _rel_err(got, ref), int(written.size)


def _serve_engine(model, name, engine_kw, lengths, new_tokens, *, dtype,
                  paged_kernel, interpret, tol):
    """warmup(), then streamed requests of the given prompt ``lengths``
    plus two that share a prefix, through one ServingEngine."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import ServingEngine

    vocab = model.config.vocab_size
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, vocab, size=n) for n in lengths]
    shared = rng.integers(3, vocab, size=max(lengths))
    tails = [rng.integers(3, vocab, size=engine_kw["block_size"] // 2 + 1)
             for _ in range(2)]
    before = metrics.snapshot("serving.")
    with ServingEngine(model, temperature=0.0, ready=False,
                       dtype=jnp.dtype(dtype), paged_kernel=paged_kernel,
                       **engine_kw) as eng:
        programs = eng.warmup()
        warm = _compile_stats()[0]
        # the different lengths and the first holder of the shared prefix
        # decode together; the second sharer arrives once the prefix is
        # registered, so its prefill is paged_prefill_extend
        handles = [eng.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        handles.append(eng.submit(np.concatenate([shared, tails[0]]),
                                  max_new_tokens=new_tokens))
        streamed = [list(h.stream(timeout=900)) for h in handles]
        handles.append(eng.submit(np.concatenate([shared, tails[1]]),
                                  max_new_tokens=new_tokens))
        streamed.append(list(handles[-1].stream(timeout=900)))
        for h, toks in zip(handles, streamed):
            _require(h.status == "DONE" and len(toks) == new_tokens,
                     f"serve[{name}]: request {h.rid} ended {h.status} "
                     f"with {len(toks)}/{new_tokens} tokens")
            _require(all(0 <= int(t) < vocab for t in toks),
                     f"serve[{name}]: token outside the vocabulary")
        served = metrics.snapshot("serving.")
        err, written = _decode_attention_check(
            eng.cache, model.config.num_heads, eng.scheduler.kernel_mode,
            dtype)
        pool_bytes = eng.cache.pool_bytes()
        pages = eng.cache.max_blocks_per_seq

    def moved(key):
        return served[key] - before.get(key, 0)

    _require(moved("serving.kernel.pallas") > 0,
             f"serve[{name}]: the Pallas decode kernel never routed in")
    _require(moved("serving.kernel.dense") == 0,
             f"serve[{name}]: decode fell to the dense route")
    _require((moved("serving.kernel.interpret") > 0) == interpret,
             f"serve[{name}]: serving.kernel.interpret moved by "
             f"{moved('serving.kernel.interpret')}, interpret={interpret}")
    _require(moved("serving.prefix.hit_blocks") > 0,
             f"serve[{name}]: the shared prefix never hit the cache")
    _require(err <= tol,
             f"serve[{name}]: Pallas vs dense decode attention differ by "
             f"{err:.3e} of the output's scale (tolerance {tol:.3e})")
    return {"table_pages": pages, "programs_warmed": programs,
            "compiles_after_warmup": _compile_stats()[0] - warm,
            "requests": len(handles), "kv_pool_bytes": pool_bytes,
            "kv_blocks_written": written,
            "prefix_hit_blocks": moved("serving.prefix.hit_blocks"),
            "pallas_vs_dense_rel_err": err}


def serve_phase(config, engines, new_tokens, *, dtype, platform,
                paged_kernel, interpret, tol):
    """Serve ``Llama(config)`` through one ServingEngine per entry of
    ``engines`` (name, ServingEngine sizing kwargs, prompt lengths).
    ``paged_kernel=None`` leaves the route to FLAGS_paged_kernel;
    ``interpret`` says whether that route is expected to interpret the
    kernel (the CPU tests) or compile it (the chip). Returns the model and
    the first-step logits of a fixed prompt for the four-chip phase."""
    import numpy as np

    from paddle_tpu.models import Llama
    from paddle_tpu.profiler import metrics

    model = _build(Llama, config, dtype)
    _require_on(platform, model, "serve")
    facts = {"layers": config.num_layers, "params": model.num_params(),
             "param_bytes": int(sum(p._data.nbytes
                                    for p in model.parameters())),
             "after_build": _hbm()}
    before = metrics.snapshot("resilience.degrade.")
    for name, engine_kw, lengths in engines:
        facts[name] = _serve_engine(
            model, name, engine_kw, lengths, new_tokens, dtype=dtype,
            paged_kernel=paged_kernel, interpret=interpret, tol=tol)
    degraded = {k: v - before.get(k, 0) for k, v in
                metrics.snapshot("resilience.degrade.").items()
                if v != before.get(k, 0)}
    _require(not degraded, f"serve: degraded paths ran: {degraded}")
    prompt = np.random.default_rng(2).integers(
        3, config.vocab_size, size=engines[0][2][0])
    logits = _first_step_logits(model, prompt)
    _require(np.isfinite(logits).all(), "serve: first-step logits not finite")
    return facts, model, prompt, logits


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _paged_case(rng, b, hq, hk, d, bs, pages, dtype):
    """Scattered-pool decode case: permuted tables over a pool whose block
    0 is the null block."""
    import jax.numpy as jnp
    import numpy as np

    nb = 1 + b * pages
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((nb, bs, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((nb, bs, hk, d)), dtype)
    tables = rng.permutation(np.arange(1, nb)).reshape(b, pages)
    return (q, k, v, jnp.asarray(tables.astype(np.int32)),
            _ragged_lens(rng, b, pages * bs))


def _check_paged(fn, hq, hk, d, bs, pages, dtype, quantized):
    import numpy as np

    from paddle_tpu.inference.paged import paged_decode_attention_dense
    from paddle_tpu.quantization import quantize_rows

    q, k, v, tables, lens = _paged_case(
        np.random.default_rng(3), 4, hq, hk, d, bs, pages, dtype)
    scales = {}
    if quantized:
        k, ks = quantize_rows(k)
        v, vs = quantize_rows(v)
        scales = dict(k_scale=ks, v_scale=vs)
    got = fn(q, k, v, tables, lens, **scales)
    return _rel_err(got, paged_decode_attention_dense(
        q, k, v, tables, lens, **scales))


def _check_flash(b, s, hq, hk, d, dtype):
    """Forward and the three gradients of causal flash attention against
    the XLA softmax attention on the same inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels.flash_attention import sdpa_xla
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention

    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), dtype)
    k, v = (jnp.asarray(rng.standard_normal((b, s, hk, d)), dtype)
            for _ in range(2))
    w = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)

    def ref_attn(q_, k_, v_):
        rep = hq // hk
        return sdpa_xla(q_, jnp.repeat(k_, rep, axis=2),
                        jnp.repeat(v_, rep, axis=2), causal=True)

    def run(attn):
        def loss(q_, k_, v_):
            out = attn(q_, k_, v_)
            return jnp.sum(out.astype(jnp.float32) * w), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (out,) + grads

    got = run(lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True))
    ref = run(ref_attn)
    return max(_rel_err(g, r) for g, r in zip(got, ref))


def _check_flash_varlen(h, d, dtype):
    """Packed ragged batch (segment-id kernel variant) against per-sequence
    XLA attention."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels.flash_attention import sdpa_xla
    from paddle_tpu.kernels.pallas.flash_attention import flash_attn_varlen

    cu = np.array([0, 200, 512, 1024], np.int32)
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((int(cu[-1]), h, d)), dtype)
               for _ in range(3))
    got = flash_attn_varlen(q, k, v, jnp.asarray(cu), jnp.asarray(cu),
                            causal=True)
    ref = jnp.concatenate([
        sdpa_xla(q[None, a:b], k[None, a:b], v[None, a:b], causal=True)[0]
        for a, b in zip(cu[:-1], cu[1:])])
    return _rel_err(got, ref)


def _check_quant_matmul(m, k, n, dtype):
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels.pallas.quant_matmul import quant_matmul

    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.1, (n,)), jnp.float32)
    ref = x.astype(jnp.float32) @ (w.astype(jnp.float32) * s[None, :])
    return _rel_err(quant_matmul(x, w, s), ref.astype(dtype))


def kernel_checks(gpt, llama, chunked_kw, paged_kw, *, dtype):
    """(name, thunk -> relative error) for every Pallas kernel of the
    repo, at the widths of the two configs and the two engines' page
    geometries. The kernels pick interpret or Mosaic from the backend."""
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_decode_attention_chunked, paged_decode_attention_kernel)

    hq, hk = llama.num_heads, llama.num_kv_heads
    d = llama.hidden_size // hq
    gh, gd = gpt.num_heads, gpt.hidden_size // gpt.num_heads
    seq = gpt.max_position_embeddings

    def pages(kw):
        return -(-kw["max_seq_len"] // kw["block_size"])

    long_bs, long_pages = chunked_kw["block_size"], pages(chunked_kw)
    short_bs, short_pages = paged_kw["block_size"], pages(paged_kw)
    per_page, chunked = (paged_decode_attention_kernel,
                         paged_decode_attention_chunked)
    return [
        ("flash_mha", lambda: _check_flash(2, seq, gh, gh, gd, dtype)),
        ("flash_gqa", lambda: _check_flash(1, seq, hq, hk, d, dtype)),
        ("flash_varlen", lambda: _check_flash_varlen(gh, gd, dtype)),
        ("paged", lambda: _check_paged(
            per_page, hq, hk, d, short_bs, short_pages, dtype, False)),
        ("paged_block16", lambda: _check_paged(
            per_page, hq, hk, d, long_bs, 8, dtype, False)),
        ("paged_chunked", lambda: _check_paged(
            chunked, hq, hk, d, long_bs, long_pages, dtype, False)),
        ("paged_q8", lambda: _check_paged(
            per_page, hq, hk, d, short_bs, short_pages, dtype, True)),
        ("paged_chunked_q8", lambda: _check_paged(
            chunked, hq, hk, d, long_bs, long_pages, dtype, True)),
        ("quant_matmul_up", lambda: _check_quant_matmul(
            8, llama.hidden_size, llama.intermediate_size, dtype)),
        ("quant_matmul_down", lambda: _check_quant_matmul(
            8, llama.intermediate_size, llama.hidden_size, dtype)),
    ]


def kernels_phase(checks, tol):
    errs = {}
    for name, thunk in checks:
        errs[name] = thunk()
        _require(errs[name] <= tol,
                 f"kernels: {name} differs from its reference by "
                 f"{errs[name]:.3e} of the output's scale "
                 f"(tolerance {tol:.3e})")
    return {"rel_err": {k: float(f"{v:.3e}") for k, v in errs.items()}}


# ---------------------------------------------------------------------------
# a hybrid model: recurrent state beside the paged cache
# ---------------------------------------------------------------------------

def hybrid_phase(config, prompt_len, steps, *, dtype, platform,
                 paged_kernel, tol):
    """A small Jamba (``models/jamba.py``): one prefill at a padded
    bucket and ``steps`` decode steps through a cache that holds
    recurrent state beside its pools, on the kernels' route
    (``paged_kernel``: the chunked scan, the in-place state update, the
    paged attention) and again on the plain route; the slot's state and
    the tokens of both must agree. Lowers and runs both selective-scan
    kernels on whatever backend this is."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.inference.paged import PagedKVCache
    from paddle_tpu.models import Jamba

    model = _build(Jamba, config, dtype)
    _require_on(platform, model, "hybrid")
    rng = np.random.default_rng(0)
    prompt = rng.integers(3, config.vocab_size, size=prompt_len)
    slots, block = 8, 16
    pages = -(-(2 * prompt_len + steps) // block)
    active = np.zeros((slots,), bool)
    runs = {}
    for mode in (paged_kernel, "dense"):
        cache = PagedKVCache(
            model.kv_cache_layers, config.num_kv_heads, config.head_dim,
            num_blocks=slots * pages + 1, block_size=block,
            max_blocks_per_seq=pages, max_batch=slots,
            dtype=jnp.dtype(dtype), recurrent_state=model.recurrent_state)
        cache.alloc_slot(block)            # an idle slot in front
        slot = cache.alloc_slot(prompt_len)
        active[:] = False
        active[slot] = True
        toks = [model.paged_prefill(cache, slot, prompt,
                                    pad_to=2 * prompt_len,
                                    kernel_mode=mode)]
        last = np.zeros((slots,), np.int64)
        for _ in range(steps):
            last[slot] = toks[-1]
            _require(cache.ensure_capacity(
                slot, int(cache.seq_lens[slot]) + 1), "hybrid: no block")
            toks.append(int(np.asarray(model.paged_decode_step(
                cache, last, active, kernel_mode=mode))[slot]))
        with cache.pool_lock:
            runs[mode] = (toks, np.array(cache.ssm_state[:, slot]),
                          np.array(cache.ssm_state[:, 0]))
    (toks, state, idle), (plain_toks, plain_state, _) = \
        runs[paged_kernel], runs["dense"]
    err = _rel_err(state, plain_state)
    _require(err <= tol, f"hybrid: the slot's state on the kernels' route "
             f"differs from the plain route's by {err:.3e} of its scale "
             f"(tolerance {tol:.3e})")
    _require(not idle.any(), "hybrid: an idle slot's state moved")
    same = sum(a == b for a, b in zip(toks, plain_toks))
    _require(same >= len(toks) - 1, f"hybrid: {len(toks) - same} of "
             f"{len(toks)} tokens differ between the two routes")
    return {"state_rel_err": float(f"{err:.3e}"), "tokens": len(toks),
            "tokens_same": same,
            "state_bytes_per_slot": cache.state_bytes() // slots}


# ---------------------------------------------------------------------------
# a latent-attention model: one row a token in the paged cache
# ---------------------------------------------------------------------------

def latent_phase(config, lengths, new_tokens, *, dtype, platform,
                 paged_kernel, tol):
    """A small Xing (``models/xing.py``: latent attention, sigmoid-routed
    experts beside a shared one, hyper-connection streams) through
    ``ServingEngine`` on the kernels' route (``paged_kernel``: the
    absorbed decode kernel over the latent pools, the grouped expert
    matmuls): prompts of ``lengths``, the second sharing the first's
    first two pages (a prefix hit through the tail-extend program).
    Then the same prompts on the plain route, fed the served tokens: at
    every decode step the served token must lie within ``tol`` of the
    plain route's maximum logit, as a share of the logits' scale."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.inference.paged import PagedKVCache
    from paddle_tpu.models import Xing
    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import ServingEngine

    model = _build(Xing, config, dtype)
    _require_on(platform, model, "latent")
    rng = np.random.default_rng(0)
    block = 16
    prompts = [rng.integers(3, config.vocab_size, size=n) for n in lengths]
    prompts[1][:2 * block] = prompts[0][:2 * block]
    longest = max(lengths) + new_tokens
    pages = -(-longest // block)
    before = metrics.snapshot("serving.")
    with ServingEngine(model, temperature=0.0, dtype=jnp.dtype(dtype),
                       max_batch=4, block_size=block,
                       max_seq_len=pages * block, bucket_cap=pages * block,
                       paged_kernel=paged_kernel) as engine:
        served = [list(engine.submit(prompts[0], max_new_tokens=new_tokens)
                       .result(timeout=600))]
        handles = [engine.submit(p, max_new_tokens=new_tokens)
                   for p in prompts[1:]]
        served += [list(h.result(timeout=600)) for h in handles]
    moved = {k: v - before.get(k, 0)
             for k, v in metrics.snapshot("serving.").items()
             if isinstance(v, (int, float))}
    _require(moved.get("serving.kernel.mla_decode.pallas", 0) > 0
             and moved.get("serving.kernel.mla_decode.plain", 0) == 0,
             "latent: the absorbed decode attention did not take the "
             "kernels' route")
    _require(moved.get("serving.prefix.hit_blocks", 0) >= 2,
             "latent: the shared pages were not a prefix hit")
    _require(moved.get("serving.moe.rows", 0) > 0,
             "latent: the decode steps counted no expert rows")
    vocab = config.vocab_size
    _require(all(len(t) == new_tokens and all(0 <= x < vocab for x in t)
                 for t in served), "latent: a request ended short")
    # the plain route, fed the served tokens
    cache = PagedKVCache(
        model.kv_cache_layers, config.num_kv_heads, config.head_dim,
        num_blocks=4 * pages + 1, block_size=block,
        max_blocks_per_seq=pages, max_batch=4, dtype=jnp.dtype(dtype),
        latent_rows=model.latent_rows)
    worst, first_same = 0.0, 0
    for prompt, toks in zip(prompts, served):
        slot = cache.alloc_slot(len(prompt))
        first_same += model.paged_prefill(
            cache, slot, prompt, kernel_mode="dense") == toks[0]
        active = np.zeros((4,), bool)
        active[slot] = True
        last = np.zeros((4,), np.int64)
        taps = []
        for tok in toks[:-1]:
            last[slot] = tok
            _require(cache.ensure_capacity(
                slot, int(cache.seq_lens[slot]) + 1), "latent: no block")
            model.paged_decode_step(cache, last, active,
                                    kernel_mode="dense",
                                    state_observer=lambda: (slot, taps))
        for (_, tap), tok in zip(taps, toks[1:]):
            logits = model.unpack_tap(tap)[1]["logits"]
            worst = max(worst, float(logits.max() - logits[tok])
                        / float(np.abs(logits).max()))
        cache.free_slot(slot)
    _require(worst <= tol, f"latent: a served token lies {worst:.3e} of "
             f"the logits' scale under the plain route's choice "
             f"(tolerance {tol:.3e})")
    _require(first_same >= len(prompts) - 1, "latent: the prefills' first "
             "tokens differ between the two routes")
    return {"margin": float(f"{worst:.3e}"),
            "tokens": sum(map(len, served)),
            "prefix_hit_blocks": int(moved["serving.prefix.hit_blocks"]),
            "moe_rows": int(moved["serving.moe.rows"]),
            "latent_bytes_per_token":
                cache.pool_bytes() // (cache.num_blocks * block)}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _shard_devices(array):
    return {s.device for s in array.addressable_shards}


def four_chip_serve(model, prompt, one_chip_logits, engine_kw, new_tokens,
                    *, dtype, tol):
    """The served Llama re-laid over a 1x4 serving mesh: tensor-parallel
    weights and KV pools, decode attention and prefill's flash kernel
    under shard_map, one KV-head group per chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import ServingEngine

    before = metrics.snapshot("serving.")
    other = np.random.default_rng(7).integers(
        3, model.config.vocab_size, size=3 * len(prompt))
    with ServingEngine(model, temperature=0.0, ready=False, mesh="1x4",
                       dtype=jnp.dtype(dtype), **engine_kw) as eng:
        eng.warmup()
        handles = [eng.submit(p, max_new_tokens=new_tokens)
                   for p in (prompt, other)]
        for h in handles:
            toks = list(h.stream(timeout=900))
            _require(h.status == "DONE" and len(toks) == new_tokens,
                     f"four_chip: request {h.rid} ended {h.status} with "
                     f"{len(toks)}/{new_tokens} tokens")
        armed = eng.scheduler.mesh.shard_map_armed
        pool_devs = _shard_devices(eng.cache.k_pools[0])
        param_devs = _shard_devices(
            model.layers[0].self_attn.q_proj.weight._data)
        in_use = [d.memory_stats()["bytes_in_use"]
                  for d in jax.devices()[:4]]
        logits = _first_step_logits(model, prompt)
    served = metrics.snapshot("serving.")
    _require(armed, "four_chip: the shard_map decode route is not armed")
    _require(served["serving.kernel.pallas"]
             > before["serving.kernel.pallas"]
             and served["serving.kernel.dense"]
             == before["serving.kernel.dense"],
             "four_chip: mesh decode did not take the Pallas route")
    _require(len(param_devs) == 4 and len(pool_devs) == 4,
             f"four_chip: q_proj on {len(param_devs)} devices, KV pool on "
             f"{len(pool_devs)}, expected 4 each")
    _require(all(b > 0 for b in in_use),
             f"four_chip: a device holds no memory: {in_use}")
    err = _rel_err(logits, one_chip_logits)
    _require(err <= tol,
             f"four_chip: first-step logits differ from one chip by "
             f"{err:.3e} of their scale (tolerance {tol:.3e})")
    return {"mesh": "1x4", "shard_map_decode": armed,
            "tp_param_devices": len(param_devs),
            "kv_pool_devices": len(pool_devs), "bytes_in_use": in_use,
            "logits_rel_err": err}


def four_chip_train(config, batch, seq, steps, one_chip_losses, *, dtype,
                    tol, expect_kernels):
    """``GPT(config)`` over a 2x2 dp x tp mesh (Megatron placements, batch
    sharded over dp) through ShardedTrainStep, the flash kernels under
    shard_map (kernels/on_mesh.py)."""
    import numpy as np

    from paddle_tpu import distributed as dist
    from paddle_tpu.models import GPT

    mesh = dist.init_mesh([2, 2], ["dp", "tp"])
    model, opt = _gpt_and_optimizer(config, dtype)
    dist.apply_placement_rules(model, GPT.tp_placement_rules(mesh), mesh)
    step = dist.ShardedTrainStep(
        model, opt, lambda m, ids: m.loss(ids, ids), mesh=mesh,
        data_placements=[dist.Shard(0), dist.Replicate()])
    ids = _gpt_batch(config, batch, seq)
    losses, recompiles = _run_steps(step, ids, steps)
    devs = _shard_devices(model.h[0].attn.qkv_proj.weight._data)
    _, calls = _pallas_calls(step.lower(ids))
    _require(len(devs) == 4,
             f"four_chip: qkv_proj on {len(devs)} devices, expected 4")
    _require(np.isfinite(losses).all() and losses[-1] < losses[0],
             f"four_chip: sharded loss not finite and falling: {losses}")
    _require(recompiles == 0,
             f"four_chip: {recompiles} XLA compiles after the second step")
    _require(calls >= len(expect_kernels),
             f"four_chip: the sharded step holds {calls} Mosaic calls, "
             f"expected {sorted(expect_kernels)}")
    err = abs(losses[0] - one_chip_losses[0]) / abs(one_chip_losses[0])
    _require(err <= tol,
             f"four_chip: first-step loss {losses[0]} vs one chip "
             f"{one_chip_losses[0]} (tolerance {tol:.3e} relative)")
    return {"mesh": "dp2 x tp2", "tp_param_devices": len(devs),
            "losses": [round(v, 4) for v in losses],
            "first_loss_rel_err": err, "mosaic_calls": calls}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _timed(name, fn, *args, **kw):
    """Run one phase and print its facts on one line."""
    count0, secs0 = _compile_stats()
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    wall = time.perf_counter() - t0
    count1, secs1 = _compile_stats()
    facts = out[0] if isinstance(out, tuple) else out
    print(json.dumps({
        "phase": name, "wall_s": round(wall, 1),
        "xla_compile_s": round(secs1 - secs0, 1),
        "other_s": round(wall - (secs1 - secs0), 1),
        "xla_compiles": count1 - count0, **_hbm(), **facts}),
        flush=True)
    return out


def main():
    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: refusing to run: jax.default_backend() is "
              f"{jax.default_backend()!r}, not 'tpu' (there is no CPU mode)",
              file=sys.stderr)
        return 2

    from paddle_tpu.models import (GPTConfig, JambaConfig, LlamaConfig,
                                   XingConfig)
    from paddle_tpu.utils import configure_compile_cache

    cache_dir = configure_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({
        "device": device, "jax": jax.__version__,
        "jaxlib": importlib.metadata.version("jaxlib"),
        "libtpu": importlib.metadata.version("libtpu"),
        "python": sys.version.split()[0],
        "compile_cache_dir": cache_dir}), flush=True)

    dtype = "bfloat16"
    gpt = GPTConfig.gpt2_medium()
    batch, seq, steps = 8, gpt.max_position_embeddings, 5
    flash = ("flash_fwd", "flash_dq", "flash_dkv")
    train = _timed("train", train_phase, gpt, batch, seq, steps,
                   dtype=dtype, platform="tpu", expect_kernels=flash)
    gc.collect()  # the 345M model and its AdamW state leave the chip

    llama = LlamaConfig.llama3_8b()
    llama.num_layers = SERVE_LAYERS
    # ServingEngine's own defaults (8 slots, block 16, 2048 tokens: a
    # 128-page table, the chunked kernel), with the bucket cap raised to
    # the context so warmup's ladder is its eight powers of two instead of
    # 71 programs; and a 512-token engine of 64-token pages (an 8-page
    # table, the per-page kernel).
    chunked_kw = dict(max_batch=8, block_size=16, max_seq_len=2048,
                      bucket_cap=2048)
    paged_kw = dict(max_batch=8, block_size=64, max_seq_len=512,
                    bucket_cap=512)
    new_tokens = 16
    _, model, prompt, logits = _timed(
        "serve", serve_phase, llama,
        [("chunked", chunked_kw, (37, 300, 900)),
         ("per_page", paged_kw, (20, 150))],
        new_tokens, dtype=dtype, platform="tpu", paged_kernel=None,
        interpret=False, tol=BF16_TOL)

    _timed("kernels", kernels_phase,
           kernel_checks(gpt, llama, chunked_kw, paged_kw, dtype=dtype),
           BF16_TOL)

    # one period of a hybrid stack at a width whose E = 512 is whole
    # lane tiles: the scan and state-update kernels' lowering, every call
    _timed("hybrid", hybrid_phase, JambaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        num_layers=4, num_heads=2, num_kv_heads=1, attn_layer_period=4,
        attn_layer_offset=2, mamba_dt_rank=16), 100, 8, dtype=dtype,
        platform="tpu", paged_kernel=None, tol=BF16_TOL)

    # a latent row of 128 + 64 (whole lane tiles, and half of one) under
    # 4 heads, 8 experts beside a shared one, 4 streams: the absorbed
    # decode kernel's lowering and the engine's path, every call
    _timed("latent", latent_phase, XingConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_layers=3, num_heads=4,
        num_kv_heads=4, q_lora_rank=128, kv_lora_rank=128,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_routed_experts=8, num_experts_per_tok=2,
        first_k_dense_replace=1), (70, 50, 120), 12, dtype=dtype,
        platform="tpu", paged_kernel=None, tol=2 * BF16_TOL)

    if device["count"] >= 4:
        _timed("four_chip_serve", four_chip_serve, model, prompt, logits,
               chunked_kw, new_tokens, dtype=dtype, tol=MESH_LOGITS_TOL)
        del model
        gc.collect()
        _timed("four_chip_train", four_chip_train, gpt, batch, seq, steps,
               train["losses"], dtype=dtype, tol=BF16_TOL,
               expect_kernels=flash)
    else:
        print(json.dumps({"phase": "four_chip",
                          "skipped": f"{device['count']} device"}),
              flush=True)

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
