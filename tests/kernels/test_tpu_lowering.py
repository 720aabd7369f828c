"""Mosaic/TPU cross-lowering CI gate.

`jax.export.export(jax.jit(fn), platforms=['tpu'])` on the CPU host runs
the full Pallas→Mosaic legalization pipeline (dtype legality, Mosaic op
verification) — the failure class interpret-mode correctness tests can't
catch. Full sweep incl. the 345M train step: tools/tpu_lowering_gate.py.

Parity stance: the reference proves its kernels by compiling .cu files
for the device (`paddle/phi/kernels/fusion/gpu/flash_attn_kernel.cu:128`);
this is the TPU equivalent, runnable without a chip.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax import export


@pytest.fixture(autouse=True)
def _force_compile(monkeypatch):
    monkeypatch.setenv("PADDLE_PALLAS_FORCE_COMPILE", "1")


def _lower(fn, *avals):
    exp = export.export(jax.jit(fn), platforms=["tpu"])(*avals)
    calls = re.findall(r"stablehlo\.custom_call @tpu_custom_call",
                       exp.mlir_module())
    return len(calls)


def _aval(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_flash_fwd_lowers_for_tpu():
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention

    q = _aval((1, 1024, 8, 128), jnp.bfloat16)
    n = _lower(lambda q, k, v: flash_attention(q, k, v, causal=True),
               q, q, q)
    assert n == 1


def test_flash_bwd_lowers_for_tpu():
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention

    q = _aval((1, 1024, 8, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32))

    n = _lower(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert n == 3  # fwd (rerun for residuals) + dq kernel + dkdv kernel


def test_flash_gqa_bwd_lowers_for_tpu():
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention

    q = _aval((1, 1024, 8, 128), jnp.bfloat16)
    kv = _aval((1, 1024, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32))

    assert _lower(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv) == 3


def test_flash_varlen_lowers_for_tpu():
    from paddle_tpu.kernels.pallas.flash_attention import flash_attn_varlen

    q = _aval((2048, 8, 128), jnp.bfloat16)
    cu = jnp.array([0, 1000, 2048], jnp.int32)
    n = _lower(lambda q, k, v: flash_attn_varlen(q, k, v, cu, cu,
                                                 causal=True), q, q, q)
    assert n == 1


def test_paged_decode_lowers_for_tpu():
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_decode_attention_kernel)

    q = _aval((4, 8, 128), jnp.bfloat16)
    kp = _aval((64, 16, 2, 128), jnp.bfloat16)  # GQA group 4
    tbl = _aval((4, 16), jnp.int32)
    lens = _aval((4,), jnp.int32)
    n = _lower(lambda q, k, v, t, l: paged_decode_attention_kernel(
        q, k, v, t, l, interpret=False), q, kp, kp, tbl, lens)
    assert n == 1


# the widths chip_smoke.py serves (Llama-3-8B heads) over its two page
# geometries: 128 pages of 16 tokens and 8 pages of 64
@pytest.mark.parametrize("bs,pages", [(16, 128), (64, 8)])
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("chunked", [False, True])
def test_paged_tier_lowers_at_smoke_widths(bs, pages, quantized, chunked):
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_decode_attention_chunked, paged_decode_attention_kernel)

    b, hq, hk, d = 8, 32, 8, 128
    nb = 1 + b * pages
    pool = _aval((nb, bs, hk, d), jnp.int8 if quantized else jnp.bfloat16)
    avals = [_aval((b, hq, d), jnp.bfloat16), pool, pool,
             _aval((b, pages), jnp.int32), _aval((b,), jnp.int32)]
    if quantized:
        avals += [_aval((nb, bs, hk), jnp.float32)] * 2
    kernel = paged_decode_attention_chunked if chunked \
        else paged_decode_attention_kernel

    def fn(q, k, v, t, l, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}
        return kernel(q, k, v, t, l, interpret=False, **kw)

    assert _lower(fn, *avals) == 1


@pytest.mark.parametrize("k,n", [(4096, 14336), (14336, 4096)])
def test_quant_matmul_lowers_at_smoke_widths(k, n):
    from paddle_tpu.kernels.pallas.quant_matmul import quant_matmul

    assert _lower(
        lambda x, w, s: quant_matmul(x, w, s, interpret=False),
        _aval((8, k), jnp.bfloat16), _aval((k, n), jnp.int8),
        _aval((n,), jnp.float32)) == 1


def test_flash_on_mesh_lowers_for_tpu():
    """On a mesh the kernels sit in a shard_map: a Mosaic call left to
    the SPMD partitioner raises at lowering."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.kernels.pallas.flash_attention import flash_attention

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    q = jax.ShapeDtypeStruct(
        (4, 1024, 8, 128), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "tp", None)))

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True,
            on_mesh=(mesh, ("dp",))).astype(jnp.float32))

    txt = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, q, q).lower(
        lowering_platforms=("tpu",)).as_text()
    assert len(re.findall(r"stablehlo\.custom_call @tpu_custom_call",
                          txt)) == 3


def test_fused_linear_ce_lowers_for_tpu():
    """The blockwise fused LM-head CE (fori/scan + dynamic_slice over W,
    online-softmax carries) must legalize for TPU in fwd AND bwd — the
    headline train step rides it (models/gpt.py loss)."""
    from paddle_tpu.nn.functional.fused_ce import (_chunk_plan, _fused_ce)

    D, V = 128, 50304  # remainder-free plan
    K, C, R = _chunk_plan(V)
    Kr, Cr, Rr = _chunk_plan(50257)  # ragged vocab exercises the epilogue

    def train(x, w, lbl):
        def f(x, w):
            return jnp.sum(_fused_ce(x, w, lbl, True, V, K, C, R, -100))
        l, (dx, dw) = jax.value_and_grad(f, argnums=(0, 1))(x, w)
        return l, dx, dw

    export.export(jax.jit(train), platforms=["tpu"])(
        _aval((256, D), jnp.bfloat16), _aval((V, D), jnp.bfloat16),
        _aval((256,), jnp.int32))

    def train_ragged(x, w, lbl):
        def f(x, w):
            return jnp.sum(_fused_ce(x, w, lbl, False, 50257, Kr, Cr,
                                     Rr, -100))
        return jax.value_and_grad(f, argnums=(0, 1))(x, w)

    export.export(jax.jit(train_ragged), platforms=["tpu"])(
        _aval((256, D), jnp.bfloat16), _aval((D, 50257), jnp.bfloat16),
        _aval((256,), jnp.int32))


# the widths the block-diffusion cell serves (SDAR-30B-A3B: 128 experts of
# 2048 x 768, 32 / 4 heads of 128): the block step's 2048 assignments in
# tiles of 16 rows, and a 2048-token prefill's 16384 in tiles of 64
@pytest.mark.parametrize("rows", [2048, 16384], ids=["step", "prefill"])
def test_moe_gmm_lowers_at_the_served_widths(rows):
    from paddle_tpu.kernels.pallas import moe_gmm as K

    e, d, f = 128, 2048, 768
    tm = K.tile_rows(rows, e)
    m = (-(-rows // tm) + e) * tm
    avals = [_aval((m, d), jnp.bfloat16), _aval((e, d, f), jnp.bfloat16),
             _aval((e, d, f), jnp.bfloat16), _aval((e, f, d), jnp.bfloat16),
             _aval((m // tm,), jnp.int32), _aval((1,), jnp.int32)]

    def fn(x, wg, wu, wd, tile_expert, num_tiles):
        h = K.moe_gmm_swiglu(x, wg, wu, tile_expert, num_tiles, tm=tm,
                             interpret=False, tag="_step")
        return K.moe_gmm(h, wd, tile_expert, num_tiles, tm=tm,
                         interpret=False, tag="_step")

    assert _lower(fn, *avals) == 2


def test_dropless_moe_lowers_with_its_sort_and_scatter():
    from paddle_tpu.distributed.moe import dropless_moe

    e, d, f = 128, 2048, 768
    avals = [_aval((256, d), jnp.bfloat16), _aval((d, e), jnp.bfloat16),
             _aval((e, d, f), jnp.bfloat16), _aval((e, d, f), jnp.bfloat16),
             _aval((e, f, d), jnp.bfloat16), _aval((256,), jnp.bool_)]

    def fn(x, router, wg, wu, wd, valid):
        return dropless_moe(x, router, wg, wu, wd, top_k=8, route="pallas",
                            valid=valid)[:2]

    assert _lower(fn, *avals) == 2


@pytest.mark.parametrize("pages", [128, 8], ids=["chunked", "per-page"])
def test_paged_block_attention_lowers_at_the_served_widths(pages):
    from paddle_tpu.inference.paged import paged_block_attention

    b, width, hq, hk, d, bs = 64, 4, 32, 4, 128, 16
    pool = _aval((1 + b * pages, bs, hk, d), jnp.bfloat16)
    avals = [_aval((b, width, hq, d), jnp.bfloat16), pool, pool,
             _aval((b, pages), jnp.int32), _aval((b,), jnp.int32)]
    n = _lower(lambda q, k, v, t, l: paged_block_attention(
        q, k, v, t, l, kernel_mode="pallas"), *avals)
    assert n == 1


def test_the_unmasking_rule_lowers_without_a_sort():
    """``low_confidence_static`` runs inside ``jit_sdar_block_step``
    (64 slots, blocks of 4, 2 denoising steps): it lowers for the TPU as
    plain comparisons, no sort, and gives back the state's own dtypes
    under 64-bit mode (the state is one int32 array fed back)."""
    from paddle_tpu.models.sdar import low_confidence_static

    b, width = 64, 4
    avals = [_aval((b, width), jnp.int32), _aval((b, width), jnp.bool_),
             _aval((b,), jnp.int32), _aval((b,), jnp.int32),
             _aval((b, width), jnp.int32), _aval((b, width), jnp.float32),
             _aval((b,), jnp.bool_)]

    def rule(*a):
        return low_confidence_static(*a, 2, 151669)
    exp = export.export(jax.jit(rule), platforms=["tpu"])(*avals)
    assert "stablehlo.sort" not in exp.mlir_module()
    assert "stablehlo.compare" in exp.mlir_module()
    (ids, masked, opened, denoised), picked = jax.eval_shape(rule, *avals)
    assert [a.dtype for a in (ids, masked, opened, denoised, picked)] \
        == [jnp.int32, jnp.bool_, jnp.int32, jnp.int32, jnp.bool_]


# -- the hybrid (state-space + attention) model's kernels, at AI21-Jamba2-3B's
# widths: E 5120, N 16, 128 slots, 26 state layers, 20 query rows on 1 KV head

@pytest.mark.parametrize("positions", [1024, 64],
                         ids=["chunks-of-128", "one-chunk"])
def test_ssm_scan_lowers_at_the_served_widths(positions):
    from paddle_tpu.kernels.pallas.selective_scan import selective_scan

    s, e, n = positions, 5120, 16
    avals = [_aval((s, e), jnp.float32), _aval((s, e), jnp.bfloat16),
             _aval((s, n), jnp.bfloat16), _aval((s, n), jnp.bfloat16),
             _aval((n, e), jnp.float32), _aval((e,), jnp.bfloat16),
             _aval((n, e), jnp.float32), _aval((), jnp.int32)]
    assert _lower(lambda *a: selective_scan(*a), *avals) == 1


def test_ssm_update_lowers_at_the_served_widths():
    from paddle_tpu.kernels.pallas.selective_scan import state_update

    layers, b, e, n = 26, 128, 5120, 16
    avals = [_aval((layers, b, n, e), jnp.float32),
             _aval((b, e), jnp.float32), _aval((b, e), jnp.bfloat16),
             _aval((b, n), jnp.bfloat16), _aval((b, n), jnp.bfloat16),
             _aval((n, e), jnp.float32), _aval((e,), jnp.bfloat16),
             _aval((b,), jnp.bool_)]
    assert _lower(lambda st, *a: state_update(st, 7, *a), *avals) == 1


def test_paged_decode_lowers_at_twenty_rows_on_one_kv_head():
    from paddle_tpu.inference.paged import paged_decode_attention

    b, hq, hk, d, bs, pages = 128, 20, 1, 128, 16, 256
    pool = _aval((1 + b * pages, bs, hk, d), jnp.bfloat16)
    avals = [_aval((b, hq, d), jnp.bfloat16), pool, pool,
             _aval((b, pages), jnp.int32), _aval((b,), jnp.int32)]
    n = _lower(lambda q, k, v, t, l: paged_decode_attention(
        q, k, v, t, l, kernel_mode="pallas"), *avals)
    assert n == 1


def test_mla_decode_lowers_at_the_served_widths():
    """32 query rows a slot over the one latent pool of 128 slots x 256
    pages: a row of 512 + 64 in 640 lanes (whole tiles: a DMA cannot
    slice half of one), a page one copy."""
    from paddle_tpu.kernels.pallas.mla_decode import mla_decode_routed

    b, h, latent, lanes, bs, pages = 128, 32, 512, 640, 16, 256
    avals = [_aval((b, h, lanes), jnp.bfloat16),
             _aval((1 + b * pages, bs, 1, lanes), jnp.bfloat16),
             _aval((b, pages), jnp.int32), _aval((b,), jnp.int32)]
    n = _lower(lambda *a: mla_decode_routed(
        *a, latent=latent, scale=0.14468, kernel_mode="pallas"), *avals)
    assert n == 1


def test_sigmoid_routed_experts_with_a_shared_one_lower_at_the_served_widths():
    """128 rows, 4 of 64 experts of 3584 x 1024 a row and a shared
    expert: the tagged pair of a decode step."""
    from paddle_tpu.distributed import moe

    t, d, f, e, k = 128, 3584, 1024, 64, 4
    avals = [_aval((t, d), jnp.bfloat16), _aval((d, e), jnp.bfloat16),
             _aval((e, d, f), jnp.bfloat16), _aval((e, d, f), jnp.bfloat16),
             _aval((e, f, d), jnp.bfloat16), _aval((e,), jnp.bfloat16),
             _aval((d, f), jnp.bfloat16), _aval((d, f), jnp.bfloat16),
             _aval((f, d), jnp.bfloat16)]

    def layer(x, router, wg, wu, wd, bias, sg, su, sd):
        return moe.dropless_moe(
            x, router, wg, wu, wd, top_k=k, route="pallas",
            kernel_tag="_decode",
            router=lambda m, rw: moe.route_sigmoid_topk(m, rw, bias, k,
                                                        True, 2.0),
            shared=(sg, su, sd))[0]

    assert _lower(layer, *avals) == 2
