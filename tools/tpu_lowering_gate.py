"""Mosaic/TPU cross-lowering gate.

Proves — on a CPU host, no TPU needed — that every Pallas kernel and the
jitted train steps legalize for TPU: ``jax.export.export(jax.jit(fn),
platforms=['tpu'])`` runs the full StableHLO lowering INCLUDING the
Pallas→Mosaic pipeline (kernel dtype legality, Mosaic op verification,
vector layout checks), the exact class of failure interpret-mode tests
cannot catch. The reference's analogue is compiling its .cu kernels:
until a kernel passes the device compiler, correctness tests in a CPU
emulator prove nothing about the device build
(`/root/reference/paddle/phi/kernels/fusion/gpu/flash_attn_kernel.cu:128`).

Run:  python tools/tpu_lowering_gate.py
Writes MOSAIC_LOWERING.md (per-gate custom-call summary + module sizes).
CI subset: tests/kernels/test_tpu_lowering.py runs the kernel gates.
"""

from __future__ import annotations

import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("PADDLE_PALLAS_FORCE_COMPILE", "1")
os.environ.setdefault("PADDLE_FLASH_FORCE", "pallas")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import export  # noqa: E402


def summarize_text(txt: str, exp) -> dict:
    calls = sorted(set(re.findall(r"stablehlo\.custom_call @(\w+)", txt))
                   - {"Sharding", "SPMDFullToShardShape",
                      "SPMDShardToFullShape"})
    return {
        "custom_calls": calls,
        "module_bytes": len(txt),
        "n_tpu_custom_calls": len(
            re.findall(r"stablehlo\.custom_call @tpu_custom_call", txt)),
        "platforms": list(exp.platforms) if exp is not None else ["tpu"],
    }


def trainstep_avals(ts, opt, ids_shape, ids_dtype=jnp.int32):
    """Abstract example args mirroring TrainStep.__call__'s signature."""
    param_objs = [p for _, p in ts._params]
    slot_states = [opt._slots_for(p) for p in param_objs]
    param_avals = [abstract(p._data.shape, p._data.dtype)
                   for p in param_objs]
    slot_avals = jax.tree.map(
        lambda a: abstract(a.shape, a.dtype), slot_states)
    buffer_avals = [abstract(b._data.shape, b._data.dtype)
                    for _, b in ts._buffers]
    key = jax.random.key(0)
    return (param_avals, slot_avals, buffer_avals,
            abstract((), jnp.float32), abstract((), jnp.float32),
            abstract(key.shape, key.dtype),
            (abstract(ids_shape, ids_dtype),))


RESULTS: list[tuple[str, dict | str]] = []


def gate(name: str, fn, *args, expect_tpu_calls: bool = True,
         extra_check=None, use_export: bool = True) -> bool:
    """extra_check(mlir_text) may raise to fail the gate or return a dict
    merged into the report row. ``use_export=False`` runs the same TPU
    lowering pipeline through jit.trace().lower() — needed for programs
    over a concrete device mesh, which jax.export would have to
    serialize device assignments for (the Mosaic legalization still runs
    either way)."""
    t0 = time.time()
    try:
        if use_export:
            exp = export.export(jax.jit(fn), platforms=["tpu"])(*args)
            txt = exp.mlir_module()
        else:
            lowered = jax.jit(fn).trace(*args).lower(
                lowering_platforms=("tpu",))
            txt = lowered.as_text()
            exp = None
        info = summarize_text(txt, exp)
        if extra_check is not None:
            extra = extra_check(txt)
            if extra:
                info.update(extra)
        info["seconds"] = round(time.time() - t0, 1)
        if expect_tpu_calls and info["n_tpu_custom_calls"] == 0:
            info["WARNING"] = ("no tpu_custom_call in module — Pallas "
                               "kernel was not routed")
            RESULTS.append((name, info))
            print(f"[gate] {name}: LOWERED BUT NO PALLAS CALL {info}")
            return False
        RESULTS.append((name, info))
        print(f"[gate] {name}: OK {info}")
        return True
    except Exception as e:  # noqa: BLE001
        msg = f"{type(e).__name__}: {e}"
        RESULTS.append((name, msg[:2000]))
        print(f"[gate] {name}: FAIL {msg[:600]}")
        return False


def abstract(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# 1. flash attention kernels
# ---------------------------------------------------------------------------

def gate_flash() -> bool:
    from paddle_tpu.kernels.pallas.flash_attention import (
        flash_attention, flash_attn_varlen)

    ok = True
    B, S, H, D = 2, 2048, 16, 128
    q = abstract((B, S, H, D), jnp.bfloat16)
    ok &= gate("flash_fwd_bf16_causal",
               lambda q, k, v: flash_attention(q, k, v, causal=True),
               q, q, q)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32))
    ok &= gate("flash_bwd_bf16_causal", jax.grad(loss, argnums=(0, 1, 2)),
               q, q, q)

    kg = abstract((B, S, 4, D), jnp.bfloat16)
    ok &= gate("flash_fwd_gqa4", lambda q, k, v: flash_attention(
        q, k, v, causal=True), q, kg, kg)

    def loss_g(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32))
    ok &= gate("flash_bwd_gqa4", jax.grad(loss_g, argnums=(0, 1, 2)),
               q, kg, kg)

    qf = abstract((B, 1024, H, D), jnp.float32)
    ok &= gate("flash_fwd_f32_noncausal",
               lambda q, k, v: flash_attention(q, k, v, causal=False),
               qf, qf, qf)

    total = 4096
    qv = abstract((total, H, D), jnp.bfloat16)
    cu = jnp.array([0, 1000, 2048, 4096], jnp.int32)
    ok &= gate("flash_varlen_bf16",
               lambda q, k, v: flash_attn_varlen(q, k, v, cu, cu,
                                                 causal=True),
               qv, qv, qv)
    return ok


# ---------------------------------------------------------------------------
# 2. paged-decode kernel
# ---------------------------------------------------------------------------

# the widths chip_smoke.py serves: Llama-3-8B heads over the two page
# geometries its engines use (128 pages of 16 tokens -> the chunked
# kernel, 8 pages of 64 -> the per-page one)
SMOKE_HEADS = dict(hq=32, hk=8, d=128)
SMOKE_PAGES = {"block16x128": (16, 128), "block64x8": (64, 8)}
SMOKE_HIDDEN, SMOKE_INTERMEDIATE = 4096, 14336


def paged_avals(bs, pages, quantized, b=8, hq=32, hk=8, d=128):
    """Abstract (q, k_pool, v_pool, tables, lens[, k_scale, v_scale])."""
    nb = 1 + b * pages
    pool = abstract((nb, bs, hk, d), jnp.int8 if quantized else jnp.bfloat16)
    out = [abstract((b, hq, d), jnp.bfloat16), pool, pool,
           abstract((b, pages), jnp.int32), abstract((b,), jnp.int32)]
    if quantized:
        out += [abstract((nb, bs, hk), jnp.float32)] * 2
    return out


def paged_call(kernel):
    def fn(q, k, v, t, l, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return kernel(q, k, v, t, l, interpret=False, **kw)
    return fn


def gate_paged() -> bool:
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_decode_attention_chunked, paged_decode_attention_kernel)

    ok = gate("paged_decode_mha",
              paged_call(paged_decode_attention_kernel),
              *paged_avals(16, 128, False, hk=32))
    for geom, (bs, pages) in SMOKE_PAGES.items():
        for quantized in (False, True):
            tag = f"{geom}{'_int8' if quantized else ''}"
            ok &= gate(f"paged_decode_{tag}",
                       paged_call(paged_decode_attention_kernel),
                       *paged_avals(bs, pages, quantized, **SMOKE_HEADS))
            ok &= gate(f"paged_chunked_{tag}",
                       paged_call(paged_decode_attention_chunked),
                       *paged_avals(bs, pages, quantized, **SMOKE_HEADS))
    return ok


def gate_mla() -> bool:
    """The absorbed latent-attention decode kernel at the widths
    ``xing29b-reasoning-saturated`` serves: 128 slots of 32 query rows
    over ONE pool of rows 640 lanes wide (512 + 64, to whole tiles), a
    256-page table; a page is one DMA the body starts itself."""
    from paddle_tpu.kernels.pallas.mla_decode import mla_decode_attention

    b, h, latent, lanes, bs, pages = 128, 32, 512, 640, 16, 256
    return gate(
        "mla_decode_block16x256",
        lambda q, pool, t, l: mla_decode_attention(
            q, pool, t, l, latent=latent, scale=0.14468, interpret=False),
        abstract((b, h, lanes), jnp.bfloat16),
        abstract((1 + b * pages, bs, 1, lanes), jnp.bfloat16),
        abstract((b, pages), jnp.int32), abstract((b,), jnp.int32))


def gate_quant_matmul() -> bool:
    from paddle_tpu.kernels.pallas.quant_matmul import quant_matmul

    ok = True
    for name, k, n in (("up", SMOKE_HIDDEN, SMOKE_INTERMEDIATE),
                       ("down", SMOKE_INTERMEDIATE, SMOKE_HIDDEN)):
        ok &= gate(f"quant_matmul_{name}",
                   lambda x, w, s: quant_matmul(x, w, s, interpret=False),
                   abstract((8, k), jnp.bfloat16), abstract((k, n), jnp.int8),
                   abstract((n,), jnp.float32))
    return ok


# ---------------------------------------------------------------------------
# 2b. the kernels on a device mesh (shard_map; a Mosaic call left to the
# SPMD partitioner raises at lowering, which is what this catches)
# ---------------------------------------------------------------------------

def gate_on_mesh() -> bool:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.inference.paged import paged_decode_attention_tp
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention
    from paddle_tpu.serving.mesh import ServingMesh

    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    q = jax.ShapeDtypeStruct(
        (8, 1024, 16, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "tp", None)))

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True,
            on_mesh=(mesh, ("dp",))).astype(jnp.float32))

    ok = gate("flash_bwd_on_mesh_dp2tp2",
              jax.grad(loss, argnums=(0, 1, 2)), q, q, q, use_export=False)

    smesh = ServingMesh(1, 4)
    bs, pages = SMOKE_PAGES["block16x128"]
    avals = paged_avals(bs, pages, False, **SMOKE_HEADS)
    avals[0] = jax.ShapeDtypeStruct(
        avals[0].shape, avals[0].dtype,
        sharding=smesh.sharding(None, "model", None))
    for i in (1, 2):
        avals[i] = jax.ShapeDtypeStruct(
            avals[i].shape, avals[i].dtype,
            sharding=smesh.kv_pool_sharding())
    ok &= gate("paged_chunked_shard_map_1x4",
               lambda q, k, v, t, l: paged_decode_attention_tp(
                   q, k, v, t, l, smesh, kernel_mode="pallas"),
               *avals, use_export=False)
    return ok


# ---------------------------------------------------------------------------
# 3. GPT-2 345M jitted train step (fwd + tape bwd + AdamW, flash inside)
# ---------------------------------------------------------------------------

def gate_train_step() -> bool:
    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.nn import functional as F

    paddle.seed(0)
    cfg = GPTConfig.gpt2_medium()
    model = GPT(cfg)
    # bf16 params: the deployment dtype on TPU (master weights live in
    # the AdamW slots)
    for _, p in model.named_parameters():
        if p._data.dtype == jnp.float32:
            p._data = p._data.astype(jnp.bfloat16)
    opt = optimizer.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                          multi_precision=True,
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))

    def step_fn(m, ids):
        logits = m(ids)
        return F.cross_entropy(logits[:, :-1, :], ids[:, 1:])

    ts = TrainStep(model, opt, step_fn)
    ts._build()
    return gate("gpt2_345m_train_step_bf16", ts._pure,
                *trainstep_avals(ts, opt, (4, 1024)))


# ---------------------------------------------------------------------------
# 3b. fp8 GPT train step (scaled e4m3 matmuls + e5m2 grads + amax state)
# ---------------------------------------------------------------------------

def gate_fp8_step() -> bool:
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPT, GPTConfig

    paddle.seed(0)
    cfg = GPTConfig.tiny()
    cfg.use_fp8 = True
    model = GPT(cfg)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    ts = TrainStep(model, opt, lambda m, ids: m.loss(ids, ids))
    ts._build()

    def check_fp8(txt):
        assert "f8E4M3FN" in txt, "no e4m3 in fp8 step"
        assert "f8E5M2" in txt, "no e5m2 grads in fp8 step"
        # the WIN CONDITION evidence (BASELINE.md fp8 note): the dot
        # itself must take f8 operands — XLA on fp8-native MXU
        # generations (v6e+) then runs it on the fp8 path, while v5e
        # legalizes it to convert+bf16-dot (the measured ~13% overhead).
        # If a cast slipped in front, the dot would take bf16 operands
        # and fp8 would be pure overhead on EVERY generation.
        f8_dots = [ln for ln in txt.splitlines()
                   if "dot_general" in ln and "f8E4M3FN" in ln]
        assert f8_dots, "no dot_general with f8 operands in fp8 step"
        return {"fp8": f"e4m3 fwd + e5m2 grads in module; "
                       f"{len(f8_dots)} f8-operand dot_general ops"}

    return gate("gpt_fp8_train_step", ts._pure,
                *trainstep_avals(ts, opt, (2, 64)),
                extra_check=check_fp8)


# ---------------------------------------------------------------------------
# 4. hybrid dp x pp x tp sharded train step (the dryrun_multichip program)
# ---------------------------------------------------------------------------

def gate_hybrid_step() -> bool:
    import numpy as _np

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer
    from paddle_tpu import distributed as dist
    from paddle_tpu.distributed.pipeline import PipelineDecoderLM
    from paddle_tpu.models import Llama, LlamaConfig
    from paddle_tpu.nn import functional as F

    paddle.seed(0)
    dp, pp, tp = 2, 2, 2
    mesh = dist.init_mesh([dp, pp, tp], ["dp", "pp", "tp"])
    config = LlamaConfig.tiny()
    model = Llama(config)
    dist.apply_placement_rules(model, Llama.tp_placement_rules(mesh), mesh)

    class Head(nn.Layer):
        def __init__(self, norm, lm_head):
            super().__init__()
            self.norm = norm
            self.lm_head = lm_head

        def forward(self, x):
            return self.lm_head(self.norm(x))

    pipe = PipelineDecoderLM(
        model.embed_tokens, model.layers, Head(model.norm, model.lm_head),
        lambda logits, labels: F.cross_entropy(logits[:, :-1, :],
                                               labels[:, 1:]),
        mesh, pp_axis="pp", num_microbatches=4, schedule="1f1b")
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=pipe.parameters(),
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))
    step = dist.ShardedTrainStep(
        pipe, opt, lambda m, ids: m.loss(ids, ids), mesh=mesh,
        data_placements=[dist.Shard(0), dist.Replicate(), dist.Shard(1)],
        shard_optimizer_axis="dp")

    ids = paddle.to_tensor(
        _np.random.default_rng(0).integers(
            0, config.vocab_size,
            (8, config.max_position_embeddings)).astype("int64"))
    # mirror ShardedTrainStep.__call__ state assembly, then export the
    # jitted pure step with the concrete placed args (tiny model)
    import jax.numpy as _jnp

    from paddle_tpu.core import random as random_mod
    from paddle_tpu.distributed.api import named_sharding

    for _, p in step._params:
        if p._dist_attr is not None:
            step._place_slots(p)
    sharding = named_sharding(step._mesh, step._data_placements, ids.ndim)
    placed = jax.device_put(ids._data, sharding)
    param_objs = [p for _, p in step._params]
    slot_states = [opt._slots_for(p) for p in param_objs]
    param_arrays = [p._data for p in param_objs]
    buffer_arrays = [b._data for _, b in step._buffers]
    t = _jnp.asarray(1.0, _jnp.float32)
    lr = _jnp.asarray(1e-3, _jnp.float32)
    key = random_mod.next_key()
    with step._mesh.jax_mesh:
        step._build()
        return gate("hybrid_dp2pp2tp2_train_step", step._jitted,
                    param_arrays, slot_states, buffer_arrays, t, lr, key,
                    (placed,), use_export=False)


# ---------------------------------------------------------------------------
# 5. expert-parallel Mixtral step (experts sharded over ep mesh axis)
# ---------------------------------------------------------------------------

def gate_ep_step() -> bool:
    import numpy as _np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu import distributed as dist
    from paddle_tpu.models import Mixtral, MixtralConfig

    paddle.seed(0)
    mesh = dist.init_mesh([2, 4], ["dp", "ep"])
    cfg = MixtralConfig.tiny()
    model = Mixtral(cfg, mesh=mesh, ep_axis="ep")
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())
    step = dist.ShardedTrainStep(
        model, opt, lambda m, ids: m.loss(ids, ids), mesh=mesh,
        data_placements=[dist.Shard(0), dist.Replicate()])

    import jax.numpy as _jnp

    from paddle_tpu.core import random as random_mod
    from paddle_tpu.distributed.api import named_sharding

    for _, p in step._params:
        if p._dist_attr is not None:
            step._place_slots(p)
    ids = paddle.to_tensor(_np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, cfg.max_position_embeddings))
        .astype("int64"))
    sharding = named_sharding(step._mesh, step._data_placements, ids.ndim)
    placed = jax.device_put(ids._data, sharding)
    param_arrays = [p._data for _, p in step._params]
    slot_states = [opt._slots_for(p) for _, p in step._params]
    buffer_arrays = [b._data for _, b in step._buffers]
    with step._mesh.jax_mesh:
        step._build()
        return gate("mixtral_ep_dp2ep4_train_step", step._jitted,
                    param_arrays, slot_states, buffer_arrays,
                    _jnp.asarray(1.0, _jnp.float32),
                    _jnp.asarray(1e-3, _jnp.float32),
                    random_mod.next_key(), (placed,), use_export=False)


# ---------------------------------------------------------------------------

def write_report(path="MOSAIC_LOWERING.md"):
    lines = [
        "# Mosaic/TPU cross-lowering evidence",
        "",
        "Produced by `tools/tpu_lowering_gate.py` on a CPU host: each gate",
        "runs `jax.export.export(jax.jit(fn), platforms=['tpu'])`, which",
        "executes the TPU lowering pipeline including the Pallas→Mosaic",
        "emission (kernel dtype legality, Mosaic op verification), or the",
        "same through `jit.trace().lower()` for programs over a device",
        "mesh. `tpu_custom_call` in the emitted StableHLO is the serialized",
        "Mosaic kernel; a gate failing raises at lowering time.",
        "",
        "What this cannot see is Mosaic's own compile inside libtpu: an",
        "i64 index-map literal in `quant_matmul` passed here and failed on",
        "the chip (PR 21). `chip_smoke.py` is the proof that a kernel",
        "compiles; this is the free check before chip time is spent.",
        "",
        f"jax {jax.__version__}; generated "
        f"{time.strftime('%Y-%m-%d %H:%M:%S')}",
        "",
        "| gate | status | tpu_custom_calls | custom calls | module bytes "
        "| lowering s |",
        "|---|---|---|---|---|---|",
    ]
    n_fail = 0
    for name, info in RESULTS:
        if isinstance(info, str):
            n_fail += 1
            lines.append(f"| {name} | **FAIL** | — | `{info[:120]}` | — "
                         "| — |")
        else:
            status = "ok" if "WARNING" not in info else "**no-pallas**"
            lines.append(
                f"| {name} | {status} | {info['n_tpu_custom_calls']} | "
                f"{', '.join(info['custom_calls'])} | "
                f"{info['module_bytes']} | {info['seconds']} |")
    lines.append("")
    with open(path, "w") as f:
        f.write("\n".join(lines))
    print(f"wrote {path} ({len(RESULTS)} gates, {n_fail} failures)")
    return n_fail


def main():
    ok = True
    ok &= gate_flash()
    ok &= gate_paged()
    ok &= gate_mla()
    ok &= gate_quant_matmul()
    ok &= gate_on_mesh()
    ok &= gate_train_step()
    ok &= gate_fp8_step()
    ok &= gate_hybrid_step()
    ok &= gate_ep_step()
    n_fail = write_report()
    sys.exit(1 if (n_fail or not ok) else 0)


if __name__ == "__main__":
    main()
