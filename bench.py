"""Driver benchmark: the BASELINE.json config ladder on one TPU chip.

Prints ONE JSON line. Headline metric: GPT-2 345M LM pretrain throughput
(tokens/s/chip + MFU). Extra rungs (reported under "ladder"): a ~770M
GPT bf16 train config, Llama-7B bf16 paged-cache decode throughput, and
ViT-L image/s train — the single-chip-feasible slice of the ladder
(GPT-2 345M -> Llama-2 7B -> 70B -> Mixtral -> ViT-L).

vs_baseline: the reference publishes no numbers (BASELINE.md). The agreed
comparator is the north-star "match or beat A100 MFU" (BASELINE.json): we
take 40% MFU — a strong published A100 result for Megatron-class GPT-345M
pretraining — as the baseline MFU, and report vs_baseline = our_MFU / 0.40.
"""

import json
import sys
import time

import jax
import numpy as np

from paddle_tpu.profiler.accounting import peak_bf16_flops
from paddle_tpu.utils.compile_cache import configure_compile_cache

BASELINE_MFU = 0.40  # A100 MFU comparator (see module docstring)


def _sync(t):
    """Wait for the device, then fetch the scalar."""
    arr = t._data if hasattr(t, "_data") else t
    return float(np.asarray(jax.block_until_ready(arr)))


def bench_gpt_train(config, batch, seq, steps, tag):
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.models import GPT

    paddle.seed(0)
    model = GPT(config)
    on_tpu = jax.default_backend() != "cpu"
    if on_tpu:
        model.to(dtype="bfloat16")  # params bf16; AdamW keeps fp32 masters
        # pre-tune flash block sizes eagerly for this model's attention
        # shape: the jitted train step then picks the tuned entry from
        # the autotune cache (incubate.autotune + kernels/pallas sweep)
        paddle.incubate.autotune.set_config({"kernel": {"enable": True}})
        from paddle_tpu.nn import functional as F
        h, hd = config.num_heads, config.hidden_size // config.num_heads
        qkv = [paddle.to_tensor(np.random.default_rng(1).standard_normal(
            (batch, seq, h, hd)).astype(np.float32)).astype("bfloat16")
            for _ in range(3)]
        with paddle.no_grad():
            F.scaled_dot_product_attention(*qkv, is_causal=True)
    opt = optimizer.AdamW(learning_rate=3e-4,
                          parameters=model.parameters(),
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    step = paddle.jit.TrainStep(model, opt,
                                lambda m, ids: m.loss(ids, ids))
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, config.vocab_size, (batch, seq)).astype("int64"))
    _sync(step(ids))
    _sync(step(ids))
    if on_tpu:
        # tracing is done (warmup compiled with the tuned blocks); turn
        # the global sweep off so later rungs never pay it mid-timing
        paddle.incubate.autotune.set_config({"kernel": {"enable": False}})
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids)
    loss_val = _sync(loss)
    dt = time.perf_counter() - t0

    tokens_per_s = batch * seq * steps / dt
    kind = jax.devices()[0].device_kind
    # utilization is a device metric: the CPU smoke reports none
    mfu = round(tokens_per_s * model.flops_per_token(seq)
                / peak_bf16_flops(kind), 4) if on_tpu else None
    return {
        "tag": tag, "tokens_per_s": round(tokens_per_s, 1),
        "mfu": mfu, "step_time_ms": round(1000 * dt / steps, 2),
        "loss": loss_val, "batch": batch, "seq": seq,
        "params": model.num_params(), "device": kind,
    }


def bench_llama_decode(config, max_batch, prompt_len, new_tokens, tag,
                       dtype="bfloat16"):
    """Paged-cache decode throughput (reference block_multihead_attention
    decode path)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference.paged import ContinuousBatchingEngine
    from paddle_tpu.models import Llama

    paddle.seed(0)
    on_chip = jax.default_backend() != "cpu"
    prev_dtype = paddle.get_default_dtype()
    if on_chip and dtype == "bfloat16":
        # construct directly in bf16: a 7B f32 init is a 27 GB transient
        # that RESOURCE_EXHAUSTEDs a 16 GB v5e before the .to() cast
        paddle.set_default_dtype("bfloat16")
    try:
        model = Llama(config)
    finally:
        paddle.set_default_dtype(prev_dtype)
    model.eval()
    if on_chip:
        model.to(dtype=dtype)
    eng = ContinuousBatchingEngine(
        model, max_batch=max_batch, block_size=32,
        max_seq_len=prompt_len + new_tokens + 32, temperature=0.0,
        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)
    rng = np.random.default_rng(0)
    for _ in range(max_batch):
        eng.add_request(
            rng.integers(0, config.vocab_size, (prompt_len,)), new_tokens)
    # prefill + first decode step compile outside the timed window
    eng.step()
    eng.step()
    done_tokens = 0
    t0 = time.perf_counter()
    while eng.has_work:
        done_tokens += len(eng.step())
    dt = time.perf_counter() - t0
    return {
        "tag": tag, "decode_tokens_per_s": round(done_tokens / dt, 1),
        "batch": max_batch, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "params": model.num_params(), "dtype": dtype,
    }


def bench_decode_tiers(max_new=24):
    """Decode speed tiers on the serving scheduler (docs/SERVING.md
    "Decode speed tiers"): the same corpus decoded base vs
    self-speculative (FLAGS_serving_spec) vs int8-KV
    (FLAGS_kv_cache_dtype) — wall tokens/s per mode, the speculative
    tokens-per-step multiple (step-count ratio on the repetitive
    corpus), and the draft acceptance rate. Appends kind
    ``decode_tiers`` to BENCH_LEDGER.jsonl; tools/regression_gate.py
    medians it with direction-aware tolerances (_per_s/_per_step/_rate
    regress DOWN)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import Llama, LlamaConfig
    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.spec import repetitive_prompts

    paddle.seed(0)
    model = Llama(LlamaConfig.tiny())
    model.eval()
    # the SAME high-acceptance corpus tools/spec_gate.py pins (greedy
    # continuation self-repetitive for the seed-0 tiny model)
    prompts = repetitive_prompts()

    def run(**kw):
        eng = ServingEngine(model, max_batch=2, block_size=8,
                            max_seq_len=64, temperature=0.0,
                            bucket_cap=32, background=False,
                            dtype=jnp.float32, **kw)
        for p in prompts:  # warm every program outside the timed
            # window (max_new 6: deep enough that the speculative
            # sweep actually engages and compiles during warmup)
            eng.submit(p, max_new_tokens=6)
            eng.run_until_idle()
        s0 = metrics.snapshot("serving.")
        t0 = time.perf_counter()
        toks = 0
        for p in prompts:  # batch-1: steps map 1:1 to decode sweeps
            h = eng.submit(p, max_new_tokens=max_new)
            eng.run_until_idle()
            toks += len(h.tokens())
        dt = time.perf_counter() - t0
        s1 = metrics.snapshot("serving.")
        eng.close()
        return toks / dt, s1["serving.steps"] - s0["serving.steps"], \
            s0, s1

    base_tps, base_steps, _, _ = run()
    # s0/s1 bracket the timed window only, so the ledgered accept rate
    # is measured over the same tokens as the throughput numbers (the
    # warmup submissions also speculate and would dilute it)
    spec_tps, spec_steps, b, a = run(spec=True)
    quant_tps, _, _, _ = run(kv_cache_dtype="int8")
    proposed = a.get("serving.spec.proposed", 0) - \
        b.get("serving.spec.proposed", 0)
    accepted = a.get("serving.spec.accepted", 0) - \
        b.get("serving.spec.accepted", 0)
    out = {
        "tag": "decode_tiers_tiny",
        "decode_base_tokens_per_s": round(base_tps, 1),
        "decode_spec_tokens_per_s": round(spec_tps, 1),
        "decode_quant_tokens_per_s": round(quant_tps, 1),
        "spec_decode_tokens_per_step": round(
            base_steps / max(spec_steps, 1), 3),
        "spec_accept_rate": round(accepted / max(proposed, 1), 3),
    }
    try:
        import os
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import bench_ledger
        bench_ledger.append_entry("decode_tiers", {
            k: v for k, v in out.items()
            if isinstance(v, (int, float))})
    except Exception:  # noqa: BLE001 — ledger trouble is advisory
        pass
    return out


def bench_quant_kernels(iters=20):
    """Pallas serving-kernel tier (docs/PERF.md): the dequant-fused
    paged decode attention vs the dense reference, and the in-register
    int8 weight matmul vs the XLA dequant-then-matmul form — per-step
    wall time each, plus the pallas/reference ratios. HONEST CPU NOTE:
    on CPU the Pallas kernels run in interpret mode, so the absolute
    times and ratios measure interpret overhead, NOT the TPU win — the
    ledger tracks them only to catch the kernel path getting
    structurally slower (tools/regression_gate.py gives the ratio
    names an explicit larger-is-worse rule). Appends kind
    ``quant_kernels`` to BENCH_LEDGER.jsonl."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.inference.paged import paged_decode_attention_dense
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_decode_attention_kernel)
    from paddle_tpu.kernels.pallas.quant_matmul import quant_matmul
    from paddle_tpu.quantization import quantize_rows

    rng = np.random.default_rng(0)
    B, HQ, HK, D, BS, MBPS = 4, 8, 4, 64, 8, 8
    NB = 1 + B * MBPS
    q = jnp.asarray(rng.standard_normal((B, HQ, D)), jnp.float32)
    k, ks = quantize_rows(jnp.asarray(
        rng.standard_normal((NB, BS, HK, D)), jnp.float32))
    v, vs = quantize_rows(jnp.asarray(
        rng.standard_normal((NB, BS, HK, D)), jnp.float32))
    tables = jnp.asarray(
        rng.permutation(np.arange(1, NB)).reshape(B, MBPS).astype(
            np.int32))
    lens = jnp.asarray(np.array([13, 41, 8, 62], np.int32))

    def timed(fn):
        fn()  # compile outside the window
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn()
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / iters * 1e6

    dense_us = timed(lambda: paged_decode_attention_dense(
        q, k, v, tables, lens, k_scale=ks, v_scale=vs))
    pallas_us = timed(lambda: paged_decode_attention_kernel(
        q, k, v, tables, lens, k_scale=ks, v_scale=vs))

    x = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
    w = jnp.asarray(rng.integers(-127, 128, (256, 512)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.01, 0.1, (512,)), jnp.float32)
    xla_mm = jax.jit(lambda xx, ww, ss: xx @ (
        ww.astype(jnp.float32) * ss[None, :]))
    xla_us = timed(lambda: xla_mm(x, w, s))
    qmm_us = timed(lambda: quant_matmul(x, w, s))

    out = {
        "tag": "quant_kernels_tiny",
        "backend": jax.default_backend(),
        "quant_decode_dense_us": round(dense_us, 1),
        "quant_decode_pallas_us": round(pallas_us, 1),
        "quant_matmul_xla_us": round(xla_us, 1),
        "quant_matmul_pallas_us": round(qmm_us, 1),
        "quant_decode_pallas_over_dense": round(
            pallas_us / max(dense_us, 1e-9), 3),
        "quant_matmul_pallas_over_xla": round(
            qmm_us / max(xla_us, 1e-9), 3),
    }
    try:
        import os
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import bench_ledger
        bench_ledger.append_entry("quant_kernels", {
            k2: v for k2, v in out.items()
            if isinstance(v, (int, float))})
    except Exception:  # noqa: BLE001 — ledger trouble is advisory
        pass
    return out


def _mesh_serve_child(n_devices):
    """One ``mesh_serve`` measurement at a fixed host-device count —
    runs in a SUBPROCESS (``bench.py --mesh-child N``) because
    ``--xla_force_host_platform_device_count`` must be set before jax
    initializes. Serves the mesh-friendly tiny Llama
    (``LlamaConfig.tiny_tp``) at ``FLAGS_serving_mesh=1xN`` (1x1 = the
    disarmed single-device baseline) and prints one JSON line."""
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import Llama, LlamaConfig
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    model = Llama(LlamaConfig.tiny_tp())
    model.eval()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(3, 250, size=s) for s in (9, 14, 7, 21)]
    eng = ServingEngine(model, max_batch=4, block_size=8, max_seq_len=64,
                        temperature=0.0, bucket_cap=32, background=False,
                        dtype=jnp.float32, mesh=f"1x{n_devices}")
    for p in prompts:  # warm every program outside the timed window
        eng.submit(p, max_new_tokens=4)
        eng.run_until_idle()
    t0 = time.perf_counter()
    hs = [eng.submit(p, max_new_tokens=16) for p in prompts]
    eng.run_until_idle()
    dt = time.perf_counter() - t0
    toks = sum(len(h.tokens()) for h in hs)
    eng.close()
    print(json.dumps({"devices": int(n_devices), "tokens": toks,
                      "elapsed_s": round(dt, 4),
                      "tokens_per_s": round(toks / dt, 2)}))


def bench_mesh_serve(device_counts=(1, 2, 4, 8), timeout_s=600):
    """Mesh-sharded serving rung (docs/SERVING.md "Mesh-sharded
    serving"): tokens/s and tokens/s/device of the tiny-TP Llama at
    1/2/4/8 forced host devices (``FLAGS_serving_mesh=1xN`` over
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``), one
    subprocess per count. Appends kind ``mesh_serve`` to
    BENCH_LEDGER.jsonl; tools/regression_gate.py medians the
    ``*_per_s`` metrics with the existing down-is-worse rate rules.
    NOTE: forced host devices SHARE the physical cores, so the CPU
    proxy shows sharding OVERHEAD, not speedup — the portable signal
    is that the sharded rungs stay within tolerance of their own
    history (the chip shows the real scaling; ROADMAP TPU flywheel)."""
    import os
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    out = {"tag": "mesh_serve_tiny_tp"}
    for n in device_counts:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={n}"])
        try:
            p = subprocess.run(
                [sys.executable, os.path.join(here, "bench.py"),
                 "--mesh-child", str(n)],
                cwd=here, env=env, capture_output=True, text=True,
                timeout=timeout_s)
            row = None
            for line in reversed((p.stdout or "").splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    row = json.loads(line)
                    break
            if row is None:
                raise RuntimeError(
                    f"child rc={p.returncode}: "
                    f"{(p.stderr or '')[-300:]}")
        except Exception as e:  # noqa: BLE001 — a dead rung reports, not raises
            out[f"mesh_d{n}_error"] = f"{type(e).__name__}: {e}"[:200]
            continue
        tps = row["tokens_per_s"]
        out[f"mesh_d{n}_tokens_per_s"] = tps
        out[f"mesh_d{n}_tokens_per_device_per_s"] = round(tps / n, 2)
    try:
        import os
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import bench_ledger
        bench_ledger.append_entry("mesh_serve", {
            k: v for k, v in out.items() if isinstance(v, (int, float))})
    except Exception:  # noqa: BLE001 — ledger trouble is advisory
        pass
    return out


def bench_vit_train(factory, batch, steps, tag, image=224):
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer

    paddle.seed(0)
    model = factory(num_classes=1000)
    if jax.default_backend() != "cpu":
        model.to(dtype="bfloat16")
    opt = optimizer.AdamW(learning_rate=3e-4,
                          parameters=model.parameters(),
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    step = paddle.jit.TrainStep(
        model, opt, lambda m, x, y: m.loss(x, y))
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.standard_normal((batch, 3, image, image)).astype("float32"))
    if jax.default_backend() != "cpu":
        # conv (like the reference's dtype-templated kernels) requires
        # input dtype == weight dtype; the model was cast to bf16 above
        x = x.astype("bfloat16")
    y = paddle.to_tensor(rng.integers(0, 1000, (batch,)).astype("int64"))
    _sync(step(x, y))
    _sync(step(x, y))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
    loss_val = _sync(loss)
    dt = time.perf_counter() - t0
    n_params = sum(p.size for p in model.parameters())
    return {
        "tag": tag, "images_per_s": round(batch * steps / dt, 1),
        "step_time_ms": round(1000 * dt / steps, 2), "loss": loss_val,
        "batch": batch, "params": n_params,
    }


def bench_eager(tag="eager"):
    """Dygraph hot-loop throughput (SURVEY hard-part #5: responsive eager
    UX when every op is an async XLA dispatch; reference comparator is the
    per-op ad_func dispatch chain, SURVEY §3.1)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer

    paddle.seed(0)
    x = paddle.to_tensor(np.ones((256, 256), np.float32))
    # single-op dispatch rate (async: don't sync per op). One warmup
    # pass first: the deferred-chain dispatch jit-compiles each chain
    # STRUCTURE once; steady state is what the rate claims.
    n = 300
    for _ in range(2):
        y = x
        t0 = time.perf_counter()
        for _ in range(n):
            y = y * 1.0001 + 0.0001
        _sync(y.sum())
        ops_per_s = 2 * n / (time.perf_counter() - t0)

    # eager train step (forward + tape backward + SGD), no jit
    net = nn.Sequential(nn.Linear(256, 256), nn.GELU(),
                        nn.Linear(256, 256))
    opt = optimizer.SGD(learning_rate=1e-3, parameters=net.parameters())
    data = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (64, 256)).astype("float32"))
    for _ in range(2):  # warm caches
        loss = net(data).square().mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    steps = 20
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = net(data).square().mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    _sync(loss)
    dt = time.perf_counter() - t0

    out = {
        "tag": tag, "eager_elementwise_ops_per_s": round(ops_per_s, 1),
        "eager_train_steps_per_s": round(steps / dt, 2),
    }
    out["defer_depth_curve_ops_per_s"] = _defer_depth_curve()
    out["async_flush_ab_ms"] = _async_flush_ab()
    out["dispatch_breakdown_us"] = _dispatch_breakdown()
    out.update(_eager_vs_jit_budget())
    _ledger_eager(out)
    return out


def _ledger_eager(out):
    """Append the eager-gap trajectory to BENCH_LEDGER.jsonl (kind
    ``eager_gap``): tools/regression_gate.py medians these with
    direction-aware tolerances (ratio regresses UP, ops/s regresses
    DOWN), so any PR that reopens the gap trips the gate. Advisory on
    failure — the bench must print its line even without a writable
    ledger."""
    try:
        import os
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools"))
        import bench_ledger
        bench_ledger.append_entry("eager_gap", {
            k: out[k] for k in (
                "eager_elementwise_ops_per_s", "eager_train_steps_per_s",
                "eager_over_jit_ratio", "eager_tiny_gpt_step_ms")
            if isinstance(out.get(k), (int, float))})
    except Exception:  # noqa: BLE001 — ledger trouble is advisory
        pass


def _async_flush_ab(n=384):
    """Async-vs-sync cap-flush A/B on the SAME dependent chain: wall
    time of a loop that crosses DEFER_CAP several times, with the flush
    worker pipelining chain execution under host capture vs
    ``FLAGS_deferred_async=0`` inline flushes. The measured delta is
    the PR-10 overlap win (the programs are identical by the partition
    contract; only who waits changes)."""
    import paddle_tpu as paddle

    x = paddle.to_tensor(np.ones((256, 256), np.float32))
    out = {}
    for mode, flag in (("async", True), ("sync", False)):
        prior = paddle.get_flags("FLAGS_deferred_async")[
            "FLAGS_deferred_async"]
        try:
            paddle.set_flags({"FLAGS_deferred_async": flag})
            y = x  # warm the chain-structure jit caches for this mode
            for _ in range(n):
                y = y * 1.0001 + 0.0001
            _sync(y.sum())
            t0 = time.perf_counter()
            y = x
            for _ in range(n):
                y = y * 1.0001 + 0.0001
            _sync(y.sum())
            out[mode] = round((time.perf_counter() - t0) * 1e3, 3)
        finally:
            paddle.set_flags({"FLAGS_deferred_async": prior})
    out["speedup"] = round(out["sync"] / out["async"], 3) \
        if out.get("async") else None
    return out


def _defer_depth_curve(n=256):
    """ops/s of a dependent elementwise chain vs the deferred-chain cap
    (core/deferred.py): the enqueue-amortization curve. Each flush pays
    one dispatch, so ops/s should grow with the cap until host-side
    capture dominates — the direct evidence that consecutive eager ops
    batch into one dispatched segment. cap=1 approximates per-op
    dispatch."""
    import paddle_tpu as paddle
    from paddle_tpu.core import deferred

    x = paddle.to_tensor(np.ones((256, 256), np.float32))
    curve = {}
    old_cap = deferred.DEFER_CAP
    try:
        for cap in (1, 8, 32, 64):
            deferred.DEFER_CAP = cap
            y = x  # warm the jit cache for this cap's chain shapes
            for _ in range(n):
                y = y * 1.0001 + 0.0001
            _sync(y.sum())
            t0 = time.perf_counter()
            y = x
            for _ in range(n):
                y = y * 1.0001 + 0.0001
            _sync(y.sum())
            curve[str(cap)] = round(2 * n / (time.perf_counter() - t0), 1)
    finally:
        deferred.DEFER_CAP = old_cap
    prior = paddle.get_flags("FLAGS_eager_defer")["FLAGS_eager_defer"]
    try:
        paddle.set_flags({"FLAGS_eager_defer": False})
        y = x
        for _ in range(n):
            y = y * 1.0001 + 0.0001
        _sync(y.sum())
        t0 = time.perf_counter()
        y = x
        for _ in range(n):
            y = y * 1.0001 + 0.0001
        _sync(y.sum())
        curve["off"] = round(2 * n / (time.perf_counter() - t0), 1)
    finally:
        paddle.set_flags({"FLAGS_eager_defer": prior})
    return curve


def _dispatch_breakdown(n=2000):
    """Per-dispatch overhead split: where a single eager
    op's wall time goes — python arg handling in apply(), the cache-key
    build, tape-node recording, and the raw jax/PJRT call underneath."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core.dispatch import _fn_key, apply
    from paddle_tpu.profiler import metrics

    x = paddle.to_tensor(np.ones((256, 256), np.float32))
    xa = x._data
    fn = jnp.tanh

    def timeit(f, k=n):
        f()  # warm
        t0 = time.perf_counter()
        for _ in range(k):
            f()
        return (time.perf_counter() - t0) / k * 1e6

    # raw jax call: the PJRT async dispatch floor
    raw = timeit(lambda: fn(xa))
    # no-grad apply: + python arg handling / amp+flags checks / wrapping;
    # the plan-cache split over the timed window shows whether the loop
    # ran on the per-call-site fast path (steady state: all hits)
    with paddle.no_grad():
        before = metrics.snapshot("dispatch.plan_cache.")
        nograd = timeit(lambda: apply(fn, x, name="tanh"))
        after = metrics.snapshot("dispatch.plan_cache.")
    plan_hit = after.get("dispatch.plan_cache.hit", 0) \
        - before.get("dispatch.plan_cache.hit", 0)
    plan_miss = after.get("dispatch.plan_cache.miss", 0) \
        - before.get("dispatch.plan_cache.miss", 0)
    # recording apply (cache hit): + key build + tape node + lazy-vjp
    x.stop_gradient = False
    rec = timeit(lambda: apply(fn, x, name="tanh"))
    # the cache key build alone
    key = timeit(lambda: _fn_key(fn), k=max(n, 5000))
    return {
        "raw_jax_call": round(raw, 2),
        "apply_nograd": round(nograd, 2),
        "apply_recording": round(rec, 2),
        "arg_handling": round(max(nograd - raw, 0.0), 2),
        "record_overhead": round(max(rec - nograd, 0.0), 2),
        "fn_key_build": round(key, 2),
        "plan_hit": int(plan_hit),
        "plan_miss": int(plan_miss),
        "plan_hit_rate": round(plan_hit / max(plan_hit + plan_miss, 1), 4),
    }


# the documented eager budget: an eager tiny-GPT train
# step must cost at most 3x its fully-jitted TrainStep equivalent
EAGER_BUDGET_RATIO = 3.0


def _eager_vs_jit_budget(steps=8):
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models import GPT, GPTConfig

    def mk():
        paddle.seed(0)
        cfg = GPTConfig.tiny()
        m = GPT(cfg)
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=m.parameters())
        ids = paddle.to_tensor(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (4, 64)).astype("int64"))
        return m, opt, ids

    m, opt, ids = mk()
    for _ in range(2):
        loss = m.loss(ids, ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = m.loss(ids, ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
    _sync(loss)
    eager_ms = (time.perf_counter() - t0) / steps * 1e3

    m, opt, ids = mk()
    step = paddle.jit.TrainStep(m, opt, lambda mm, i: mm.loss(i, i))
    step(ids); step(ids)  # compile + settle
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids)
    _sync(loss)
    jit_ms = (time.perf_counter() - t0) / steps * 1e3
    ratio = eager_ms / jit_ms if jit_ms > 0 else float("inf")
    return {
        "eager_tiny_gpt_step_ms": round(eager_ms, 2),
        "jitted_tiny_gpt_step_ms": round(jit_ms, 2),
        "eager_over_jit_ratio": round(ratio, 2),
        "eager_budget_ratio": EAGER_BUDGET_RATIO,
        "eager_budget_pass": bool(ratio <= EAGER_BUDGET_RATIO),
    }


def _scan_timed(fn, arrs, iters):
    """Time ``fn(*arrs)`` as one jitted lax.scan of ``iters`` serialized
    calls ending in a scalar fetch: host dispatch is paid once, so the
    quotient is device time per call. The carry feeds the first operand
    so XLA cannot hoist the loop-invariant call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def many(*a):
        def body(c, _):
            first = a[0] + c.astype(a[0].dtype) * a[0].dtype.type(0)
            o = fn(first, *a[1:])
            return o.astype(jnp.float32).mean(), None
        c, _ = jax.lax.scan(body, jnp.float32(0), None, length=iters)
        return c

    float(many(*arrs))  # compile + warm
    t0 = time.perf_counter()
    float(many(*arrs))
    return (time.perf_counter() - t0) / iters


def bench_flash_ab(batch=4, seq=2048, heads=16, head_dim=64, iters=20,
                   tag="flash_ab"):
    """Pallas flash kernel vs the stock XLA attention on the same shapes
    (the kernel must justify itself with an on/off delta). Times the
    kernel fns directly with the jitted-scan method."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import sdpa_xla
    from paddle_tpu.kernels.pallas.flash_attention import (
        flash_attention as pallas_flash)

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal(
        (batch, seq, heads, head_dim)), jnp.bfloat16) for _ in range(3))

    # one eager (concrete-array) call first: the runtime block sweep
    # only fires outside a jit trace, and its winners persist to the
    # autotune file cache — without this the scan-timed leg measures
    # the static default blocks at this shape
    pallas_flash(q, k, v, causal=True).block_until_ready()

    t_pallas = _scan_timed(
        lambda a, b, c: pallas_flash(a, b, c, causal=True), (q, k, v),
        iters)
    t_xla = _scan_timed(
        lambda a, b, c: sdpa_xla(a, b, c, causal=True), (q, k, v), iters)
    return {
        "tag": tag, "batch": batch, "seq": seq, "heads": heads,
        "head_dim": head_dim,
        "pallas_ms": round(t_pallas * 1e3, 3),
        "xla_ms": round(t_xla * 1e3, 3),
        "pallas_speedup": round(t_xla / t_pallas, 3),
    }


def bench_paged_ab(batch=4, context=2048, heads=32, kv_heads=32,
                   head_dim=128, block_size=32, iters=20, tag="paged_ab"):
    """Pallas paged-decode kernel vs the dense gather+einsum path at long
    context (the kernel must beat the einsum path)."""
    import jax.numpy as jnp

    from paddle_tpu.inference.paged import (paged_decode_attention,
                                            paged_decode_attention_dense)

    rng = np.random.default_rng(0)
    mbps = context // block_size
    nb = batch * mbps + 1
    kp = jnp.asarray(rng.standard_normal(
        (nb, block_size, kv_heads, head_dim)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal(
        (nb, block_size, kv_heads, head_dim)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal(
        (batch, heads, head_dim)), jnp.bfloat16)
    tbl = np.zeros((batch, mbps), np.int32)
    for i in range(batch):
        tbl[i] = np.arange(1 + i * mbps, 1 + (i + 1) * mbps)
    tbl = jnp.asarray(tbl)
    lens = jnp.full((batch,), context - 7, jnp.int32)

    t_kernel = _scan_timed(
        lambda qq, *a: paged_decode_attention(qq, *a, kernel_mode="pallas"),
        (q, kp, vp, tbl, lens), iters)
    t_dense = _scan_timed(
        lambda qq, *a: paged_decode_attention_dense(qq, *a),
        (q, kp, vp, tbl, lens), iters)
    return {
        "tag": tag, "batch": batch, "context": context,
        "heads": heads, "kv_heads": kv_heads, "block_size": block_size,
        "kernel_ms": round(t_kernel * 1e3, 3),
        "dense_ms": round(t_dense * 1e3, 3),
        "kernel_speedup": round(t_dense / t_kernel, 3),
    }


def bench_ce_fusion_ab(steps=10):
    """Same-day A/B: the headline 345M config with the blockwise fused
    LM-head CE (models/gpt.py fused_head_ce) vs the dense-logits path.
    One child process, sequential legs with explicit teardown (two
    resident 345M AdamW states would crowd 16 GB HBM)."""
    import gc

    from paddle_tpu.models import GPTConfig

    res = {}
    for fused in (True, False):
        cfg = GPTConfig.gpt2_medium()
        cfg.fused_head_ce = fused
        leg = "fused" if fused else "dense"
        res[leg] = _try(bench_gpt_train, cfg, 8, 1024, steps,
                        f"gpt2_345m_ce_{leg}")
        gc.collect()
    if all("step_time_ms" in res[k] for k in ("fused", "dense")):
        res["fused_speedup"] = round(
            res["dense"]["step_time_ms"] / res["fused"]["step_time_ms"], 3)
    else:
        res["skipped"] = "ce_fusion_ab leg failed: " + "; ".join(
            f"{k}={res[k].get('skipped', 'ok')[:120]}"
            for k in ("fused", "dense") if isinstance(res.get(k), dict))
    res["tag"] = "ce_fusion_ab"
    return res


def _try(fn, *args, **kwargs):
    try:
        return fn(*args, **kwargs)
    except Exception as e:  # OOM etc: report, don't kill the headline
        return {"tag": kwargs.get("tag") or (args[-1] if args else "?"),
                "skipped": f"{type(e).__name__}: {e}"[:300]}


def _tpu_rung_specs():
    """Ordered (name, thunk) list for the TPU ladder. Called inside the
    per-rung CHILD process (run_rung) — each rung gets the chip and its
    HBM to itself; in-process sequencing left earlier rungs' models
    resident and RESOURCE_EXHAUSTED'd everything after the 770M rung."""
    from paddle_tpu.models import GPTConfig, LlamaConfig
    from paddle_tpu.vision.models import vit_l_16

    fp8_cfg = GPTConfig.gpt2_medium()
    fp8_cfg.use_fp8 = True

    return [
        ("head",
         lambda: bench_gpt_train(GPTConfig.gpt2_medium(), 8, 1024, 20,
                                 "gpt2_345m")),
        ("gpt_345m_fp8_train",
         lambda: bench_gpt_train(fp8_cfg, 8, 1024, 10, "gpt2_345m_fp8")),
        ("gpt_770m_train",
         lambda: bench_gpt_train(GPTConfig.gpt2_large(), 4, 1024, 10,
                                 "gpt2_770m")),
        ("llama7b_decode",
         lambda: bench_llama_decode(LlamaConfig.llama2_7b(), 4, 128, 128,
                                    "llama2_7b_decode")),
        ("vit_l_train", lambda: bench_vit_train(vit_l_16, 32, 10,
                                                "vit_l_16")),
        ("flash_ab", bench_flash_ab),
        ("paged_ab", bench_paged_ab),
        ("ce_fusion_ab", bench_ce_fusion_ab),
        ("eager", bench_eager),
    ]


def run_rung(name, out_path):
    """Child-process entry: execute ONE ladder rung on the chip, dump its
    JSON. A child that did not get the chip fails its rung: a CPU number
    is never a ladder row."""
    configure_compile_cache()
    if jax.default_backend() != "tpu":
        res = {"skipped": f"rung child resolved backend "
                          f"{jax.default_backend()!r}, not 'tpu'"}
    else:
        res = _try(dict(_tpu_rung_specs())[name])
    if "skipped" not in res:
        dev = jax.devices()[0]
        res.setdefault("peak_hbm_bytes",
                       dev.memory_stats()["peak_bytes_in_use"])
        res.setdefault("backend", jax.default_backend())
        res.setdefault("device", dev.device_kind)
    with open(out_path, "w") as f:
        json.dump(res, f)


def _run_rung_subprocess(name, timeout_s=1500):
    import os
    import subprocess
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    fd, out_path = tempfile.mkstemp(suffix=f"_{name}.json")
    os.close(fd)
    os.unlink(out_path)
    code = f"import bench; bench.run_rung({name!r}, {out_path!r})"
    try:
        try:
            p = subprocess.run([sys.executable, "-c", code], cwd=here,
                               capture_output=True, text=True,
                               timeout=timeout_s)
        except subprocess.TimeoutExpired:
            return {"skipped": f"rung subprocess timed out after "
                               f"{timeout_s}s"}
        try:
            if os.path.exists(out_path):
                with open(out_path) as f:
                    return json.load(f)
        except (OSError, ValueError):
            pass
        return {"skipped": f"rung subprocess rc={p.returncode}: "
                           f"{(p.stderr or '')[-400:]}"}
    finally:
        try:
            if os.path.exists(out_path):
                os.unlink(out_path)
        except OSError:
            pass


def _probe_backend_subprocess(timeout_s=240):
    """Resolve the backend in a THROWAWAY child process: a chip belongs
    to one process at a time, so a parent that initialized the TPU client
    itself would starve every per-rung child. Returns the backend name,
    or None when the child printed none."""
    import os
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    try:
        p = subprocess.run(
            [sys.executable, "-c",
             "import jax; print('BACKEND=' + jax.default_backend())"],
            cwd=here, capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return None
    for line in (p.stdout or "").splitlines():
        if line.startswith("BACKEND="):
            return line.split("=", 1)[1].strip()
    return None


def _result_line(metric, unit, vs_baseline, head, ladder):
    return {
        "metric": metric, "value": head["tokens_per_s"], "unit": unit,
        "vs_baseline": vs_baseline,
        "mfu": head["mfu"], "device": head["device"],
        "step_time_ms": head["step_time_ms"], "loss": head["loss"],
        "batch": head["batch"], "seq": head["seq"],
        "params": head["params"], "ladder": ladder,
    }


def _cpu_smoke():
    """The explicit ``JAX_PLATFORMS=cpu`` line: tiny models, in process,
    under a metric name that cannot be parsed as the 345M headline and
    with no utilization (``mfu`` is None off the chip)."""
    from paddle_tpu.models import GPTConfig, LlamaConfig

    head = bench_gpt_train(GPTConfig.tiny(), 2, 64, 3, "gpt2_tiny")
    ladder = {
        "llama_decode_smoke": _try(
            bench_llama_decode, LlamaConfig.tiny(), 2, 8, 8,
            "llama_tiny_decode", dtype="float32"),
        "decode_tiers": _try(bench_decode_tiers),
        "quant_kernels": _try(bench_quant_kernels),
        "mesh_serve": _try(bench_mesh_serve),
    }
    fp8_cfg = GPTConfig.tiny()
    fp8_cfg.use_fp8 = True
    ladder["gpt_fp8_smoke"] = _try(
        bench_gpt_train, fp8_cfg, 2, 64, 3, "gpt_tiny_fp8")
    ladder["eager"] = _try(bench_eager)
    return _result_line("cpu_smoke_gpt_tiny_tokens_per_sec",
                        "tokens/s (cpu smoke, tiny model)", None, head,
                        ladder)


def main():
    """Exit code 0 with one JSON line when the headline rung ran on the
    chip (or when ``JAX_PLATFORMS=cpu`` explicitly asked for the CPU
    smoke line); non-zero with the reason on stderr otherwise. On the chip
    path this process never initializes a backend: every rung runs in a
    child of its own."""
    import os

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        configure_compile_cache()
        print(json.dumps(_cpu_smoke()))
        return 0

    backend = _probe_backend_subprocess()
    if backend != "tpu":
        print(f"bench: no chip: a child process resolved backend "
              f"{backend!r}, not 'tpu' (set JAX_PLATFORMS=cpu for the "
              f"distinctly named CPU smoke line)", file=sys.stderr)
        return 1

    head = _run_rung_subprocess("head")
    if "tokens_per_s" not in head:
        print(f"bench: the headline rung failed: "
              f"{head.get('skipped', head)}", file=sys.stderr)
        return 1
    ladder = {name: _run_rung_subprocess(name)
              for name, _ in _tpu_rung_specs() if name != "head"}
    print(json.dumps(_result_line(
        "gpt2_345m_pretrain_tokens_per_sec_per_chip", "tokens/s/chip",
        round(head["mfu"] / BASELINE_MFU, 4), head, ladder)))
    return 0


if __name__ == "__main__":
    if "--mesh-child" in sys.argv:
        _mesh_serve_child(int(sys.argv[sys.argv.index("--mesh-child") + 1]))
    else:
        sys.exit(main())
