"""Pallas serving-kernel tier (docs/PERF.md): interpret-mode parity of
the dequant-fused paged-attention decode kernel, the chunked
flash-decode variant, and the in-register int8 weight matmul against
their dense/XLA references — plus the FLAGS_paged_kernel routing
contract (counters move on the pallas route, stay silent forced-dense,
tokens identical either way).

Every kernel here runs under ``interpret=True`` on CPU, so the parity
matrix is tier-1: the same kernel bodies Mosaic compiles on TPU execute
(slowly) as jax ops. tools/kernel_gate.py pins the engine-level subset
of these as a standalone gate.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from tests.framework.conftest import tiny_engine

jnp = pytest.importorskip("jax.numpy")


PROMPT = [3, 17, 9, 42, 7]


# ---------------------------------------------------------------------------
# kernel-level parity matrix
# ---------------------------------------------------------------------------

def _case(B, HQ, HK, D, BS, MBPS, lens, seed=0):
    """Scattered-pool decode case: block 0 is the null block, each
    slot's pages land at permuted pool indices (the kernel must follow
    the table, not the layout)."""
    rng = np.random.default_rng(seed)
    NB = 1 + B * MBPS
    q = jnp.asarray(rng.standard_normal((B, HQ, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((NB, BS, HK, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((NB, BS, HK, D)), jnp.float32)
    tables = np.zeros((B, MBPS), np.int32)
    perm = rng.permutation(np.arange(1, NB))
    for b in range(B):
        tables[b] = perm[b * MBPS:(b + 1) * MBPS]
    return (q, k, v, jnp.asarray(tables),
            jnp.asarray(np.asarray(lens, np.int32)))


# GQA ratios (MHA / GQA4 / MQA) x ragged lengths including an inactive
# slot (len 0) and exact block-boundary lengths
_MATRIX = [
    (2, 8, 8, 32, 8, 6, [13, 41]),          # MHA, ragged
    (3, 8, 2, 32, 8, 6, [0, 16, 47]),       # GQA4, len-0 + boundary
    (2, 8, 1, 32, 8, 6, [8, 48]),           # MQA, boundary + full
    (2, 4, 4, 64, 16, 4, [1, 33]),          # larger pages
]


@pytest.mark.parametrize("B,HQ,HK,D,BS,MBPS,lens", _MATRIX)
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("chunked", [False, True])
def test_kernel_parity_matrix(B, HQ, HK, D, BS, MBPS, lens, quantized,
                              chunked):
    from paddle_tpu.inference.paged import paged_decode_attention_dense
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_decode_attention_chunked, paged_decode_attention_kernel)

    q, k, v, tables, lens_j = _case(B, HQ, HK, D, BS, MBPS, lens)
    scales = {}
    if quantized:
        from paddle_tpu.quantization import quantize_rows
        k, ks = quantize_rows(k)
        v, vs = quantize_rows(v)
        scales = dict(k_scale=ks, v_scale=vs)
    ref = paged_decode_attention_dense(q, k, v, tables, lens_j, **scales)
    if chunked:
        got = paged_decode_attention_chunked(
            q, k, v, tables, lens_j, interpret=True, chunk_pages=2,
            **scales)
    else:
        got = paged_decode_attention_kernel(
            q, k, v, tables, lens_j, interpret=True, **scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=5e-5, rtol=1e-4)


def test_chunked_default_pick_matches_dense():
    """chunk_pages=None -> pick_chunk_pages; the table pads to a chunk
    multiple with null pages, which must not perturb the output."""
    from paddle_tpu.inference.paged import paged_decode_attention_dense
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_decode_attention_chunked, pick_chunk_pages)

    q, k, v, tables, lens_j = _case(2, 8, 4, 32, 8, 7, [19, 50])
    cp = pick_chunk_pages(7, 8, 4, 32)
    assert cp >= 2  # tiny tiles: the budget never forces cp=1
    ref = paged_decode_attention_dense(q, k, v, tables, lens_j)
    got = paged_decode_attention_chunked(q, k, v, tables, lens_j,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=5e-5, rtol=1e-4)


def test_pick_chunk_pages_budget():
    from paddle_tpu.kernels.pallas.paged_attention import pick_chunk_pages

    # huge tiles blow the VMEM budget down to single-page stepping
    assert pick_chunk_pages(64, 512, 32, 256) == 1
    # and the pick never exceeds the table length
    assert pick_chunk_pages(3, 8, 4, 32) <= 3
    # the benchmark's geometries: dense 32 KiB / 16 KiB pages and the
    # [rows, keys] score tile leave room for the longest candidate
    assert pick_chunk_pages(128, 16, 8, 128, 2, rows=32) == 32
    assert pick_chunk_pages(128, 16, 4, 128, 2, rows=128) == 32
    # the score tile counts: more query rows a slot, fewer pages a step
    assert pick_chunk_pages(128, 16, 8, 128, 2, rows=1024) \
        < pick_chunk_pages(128, 16, 8, 128, 2, rows=32)
    # so do the pools' bytes: MHA at 32 heads holds 128 KiB pages
    assert pick_chunk_pages(128, 16, 32, 128, 2, rows=32) == 8


# the two serving cells' attention geometries (16-token pages, 128-page
# tables): Mistral-7B decode (32 query / 8 KV heads of 128) and the SDAR
# block step (4 x 8 folded rows a KV head, 4 KV heads)
_GEOMETRIES = {"mistral": (32, 8), "sdar_block": (128, 4)}
# slot lengths around a page's and a chunk's edges (the default pick is
# 32 pages = 512 tokens a grid step), an idle slot, a full table; every
# batch's slots differ
_LENS = {"page_edges": [0, 1, 15, 16, 17],
         "chunk_edges": [511, 512, 513, 2048]}


@pytest.mark.parametrize("lens", sorted(_LENS))
@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
def test_benchmark_geometry_matches_dense(geometry, pool, lens):
    """The chunked kernel as the cells call it (chunk length picked from
    the shapes) against the dense reference: bf16 pools with bf16
    queries, int8 pools with their scale rows."""
    from paddle_tpu.inference.paged import paged_decode_attention_dense
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_decode_attention_chunked)

    rows, hk = _GEOMETRIES[geometry]
    q, k, v, tables, lens_j = _case(len(_LENS[lens]), rows, hk, 128, 16,
                                    128, _LENS[lens])
    scales, tol = {}, dict(atol=5e-5, rtol=1e-4)
    if pool == "int8":
        from paddle_tpu.quantization import quantize_rows
        k, ks = quantize_rows(k)
        v, vs = quantize_rows(v)
        scales = dict(k_scale=ks, v_scale=vs)
    else:
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        tol = dict(atol=3e-2, rtol=3e-2)
    ref = paged_decode_attention_dense(q, k, v, tables, lens_j, **scales)
    got = paged_decode_attention_chunked(q, k, v, tables, lens_j,
                                         interpret=True, **scales)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), **tol)
    idle = [i for i, n in enumerate(_LENS[lens]) if n == 0]
    assert not np.asarray(got, np.float32)[idle].any()


@pytest.mark.parametrize("chunked", [False, True])
@pytest.mark.parametrize("hq,hk", [(16, 16), (8, 2), (8, 1), (12, 3)],
                         ids=["mha", "tp_shard", "mqa", "three_kv_heads"])
def test_head_layouts_match_dense(hq, hk, chunked):
    """One matmul for all KV heads under a head mask: every query row
    sums over its own KV head's keys only, whatever the grouping — MHA
    (a row a head), the 2 KV heads of a tensor-parallel shard, MQA, and
    a head count that is no power of two."""
    from paddle_tpu.inference.paged import paged_decode_attention_dense
    from paddle_tpu.kernels.pallas.paged_attention import (
        paged_decode_attention_chunked, paged_decode_attention_kernel)

    q, k, v, tables, lens_j = _case(3, hq, hk, 128, 16, 20, [0, 17, 300])
    ref = paged_decode_attention_dense(q, k, v, tables, lens_j)
    kernel = paged_decode_attention_chunked if chunked \
        else paged_decode_attention_kernel
    got = kernel(q, k, v, tables, lens_j, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=5e-5, rtol=1e-4)


def test_quant_matmul_matches_xla_dequant():
    from paddle_tpu.kernels.pallas.quant_matmul import quant_matmul

    rng = np.random.default_rng(1)
    for m_shape, K, N in [((3, 5), 96, 200), ((1,), 32, 8),
                          ((2, 130), 64, 128)]:
        x = jnp.asarray(rng.standard_normal((*m_shape, K)), jnp.float32)
        w = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
        s = jnp.asarray(rng.uniform(0.01, 0.1, (N,)), jnp.float32)
        ref = x @ (w.astype(jnp.float32) * s[None, :])
        got = quant_matmul(x, w, s, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4, rtol=1e-5)


def test_converted_linear_routes_quant_matmul():
    """ConvertedInt8Linear under FLAGS_paged_kernel=pallas matches its
    own XLA dequant-then-matmul form (the dense-route output)."""
    from paddle_tpu import nn
    from paddle_tpu.quantization import ConvertedInt8Linear

    paddle.seed(0)
    src = nn.Linear(24, 40)
    x = paddle.randn([5, 24])
    saved = paddle.get_flags(["FLAGS_paged_kernel"])
    try:
        paddle.set_flags({"FLAGS_paged_kernel": "dense"})
        ref = ConvertedInt8Linear(src)(x)
        paddle.set_flags({"FLAGS_paged_kernel": "pallas"})
        lin = ConvertedInt8Linear(src)
        assert lin._kernel_route in ("pallas", "interpret")
        got = lin(x)
    finally:
        paddle.set_flags(saved)
    np.testing.assert_allclose(np.asarray(got._data),
                               np.asarray(ref._data),
                               atol=1e-4, rtol=1e-5)


# ---------------------------------------------------------------------------
# routing contract
# ---------------------------------------------------------------------------

def test_resolve_and_route():
    from paddle_tpu.inference.paged import (kernel_route,
                                            resolve_paged_kernel)

    assert resolve_paged_kernel("pallas") == "pallas"
    assert resolve_paged_kernel(None) in ("auto", "pallas", "dense")
    with pytest.raises(ValueError):
        resolve_paged_kernel("cuda")
    assert kernel_route("dense") == "dense"
    # forced pallas on CPU runs the kernel in interpret mode
    import jax
    if jax.default_backend() == "cpu":
        assert kernel_route("pallas") == "interpret"
        assert kernel_route("auto") == "dense"


def _serve(model, max_new=12, **kw):
    eng = tiny_engine(model, **kw)
    h = eng.submit(PROMPT, max_new)
    eng.run_until_idle()
    toks = h.result()
    eng.close()
    return toks


def _kernel_counters():
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot("serving.kernel")
    return {k: snap.get(k, 0) for k in
            ("serving.kernel.pallas", "serving.kernel.dense",
             "serving.kernel.interpret")}


def test_quantized_serve_routes_pallas_and_matches_dense(tiny_llama):
    """THE acceptance pin: an int8-KV engine with the kernel routed in
    serves the same tokens as the dense reference, and the
    serving.kernel.pallas counter moves."""
    before = _kernel_counters()
    toks_pal = _serve(tiny_llama, kv_cache_dtype="int8",
                      paged_kernel="pallas")
    after = _kernel_counters()
    assert after["serving.kernel.pallas"] > \
        before["serving.kernel.pallas"]
    toks_dense = _serve(tiny_llama, kv_cache_dtype="int8",
                        paged_kernel="dense")
    assert toks_pal == toks_dense
    assert len(toks_pal) == 12


def test_forced_dense_counter_silence(tiny_llama):
    """FLAGS_paged_kernel=dense is the byte-for-byte revert: no
    serving.kernel.* counter moves at all."""
    before = _kernel_counters()
    _serve(tiny_llama, kv_cache_dtype="int8", paged_kernel="dense")
    assert _kernel_counters() == before


def test_fp32_serve_kernel_matches_dense(tiny_llama):
    toks_pal = _serve(tiny_llama, paged_kernel="pallas")
    toks_auto = _serve(tiny_llama)
    assert toks_pal == toks_auto


def test_int8_kernel_serve_deterministic(tiny_llama):
    """Greedy int8 decode through the Pallas route is run-to-run
    deterministic (the online-softmax accumulation order is fixed)."""
    a = _serve(tiny_llama, kv_cache_dtype="int8", paged_kernel="pallas")
    b = _serve(tiny_llama, kv_cache_dtype="int8", paged_kernel="pallas")
    assert a == b


def test_flag_routes_engine(tiny_llama):
    """The engine reads FLAGS_paged_kernel at construction (no ctor
    kwarg needed), and the decode_step spans carry the route."""
    saved = paddle.get_flags(["FLAGS_paged_kernel"])
    # counters move at TRACE time (one movement per compiled program) —
    # drop the cached decode programs so this engine's first step
    # retraces and the movement is observable
    tiny_llama.paged_programs.clear()
    try:
        paddle.set_flags({"FLAGS_paged_kernel": "pallas"})
        eng = tiny_engine(tiny_llama, kv_cache_dtype="int8")
        assert eng._sched.kernel_mode == "pallas"
        import jax
        if jax.default_backend() == "cpu":
            assert eng._sched.kernel_route == "interpret"
        before = _kernel_counters()
        h = eng.submit(PROMPT, 4)
        eng.run_until_idle()
        assert len(h.result()) == 4
        assert _kernel_counters()["serving.kernel.pallas"] > \
            before["serving.kernel.pallas"]
        eng.close()
    finally:
        paddle.set_flags(saved)
