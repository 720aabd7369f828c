"""The donated serving programs, compiled for a described TPU v5e with no
chip attached (ISSUE 27): at the benchmark's attention geometry (32 query
/ 8 KV heads of 128, a pool of 4097 blocks of 16 tokens, 32 slots of 128
pages) the chip's compiler pairs every pool with an output and leaves no
whole-pool ``copy`` in front of the scatter or the Pallas call — the
copy that was the largest device operation of both serving cells
(PERF.md, PR 26). Nothing runs; a time comes only from the chip.

The only file that describes a topology: the process that does holds the
TPU library until it exits, so these stay together and the call stays in
a fixture (``on-chip-measurement`` guide, section 2).
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle

LAYERS, SLOTS, PAGES, BLOCK, BLOCKS = 1, 32, 128, 16, 4097
POOL = (BLOCKS, BLOCK, 8, 128)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model():
    """Mistral-7B's attention geometry; MLP and vocabulary cut down (they
    touch no pool) so the parameters are 60M, not 7B."""
    from paddle_tpu.models import Llama, LlamaConfig

    paddle.seed(0)
    m = Llama(LlamaConfig(vocab_size=1024, hidden_size=4096,
                          intermediate_size=1024, num_layers=LAYERS,
                          num_heads=32, num_kv_heads=8,
                          max_position_embeddings=4096, rope_theta=1e6))
    m.eval()
    return m


def _lower(model, program, one_chip, quantized):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = [s(POOL, jnp.int8 if quantized else jnp.bfloat16)] * LAYERS
    scales = [s(POOL[:3], jnp.float32)] * LAYERS if quantized else []
    arrs = model._param_arrays()
    params = tuple(s(a.shape, jnp.bfloat16) for a in arrs)
    key = jax.random.key(0)
    key, temp = s(key.shape, key.dtype), s((), jnp.float32)
    try:
        if program == "decode":
            return model.serving_program(
                "decode", quantized, "pallas")._jitted.lower(
                params, s((SLOTS,), jnp.int32), pools, pools, scales,
                scales, s((SLOTS, PAGES), jnp.int32),
                s((SLOTS,), jnp.int32), s((SLOTS,), jnp.bool_), key, temp)
        return model.serving_program("prefill", quantized)._jitted.lower(
            params, s((1, 512), jnp.int64), s((), jnp.int32),
            s((PAGES,), jnp.int32), pools, pools, scales, scales, key,
            temp)
    finally:
        model._param_rebind()(arrs)


@pytest.mark.parametrize("program, quantized", [
    ("decode", False), ("decode", True), ("prefill", False),
    ("prefill", True)],
    ids=["decode", "decode-int8", "prefill", "prefill-int8"])
def test_the_v5e_compiler_aliases_every_pool_and_copies_none(
        monkeypatch, one_chip, model, program, quantized):
    monkeypatch.setenv("PADDLE_PALLAS_FORCE_COMPILE", "1")
    compiled = _lower(model, program, one_chip, quantized).compile()
    text = compiled.as_text()
    header = text[:text.index("\n")]
    aliased = re.findall(r"\{\d+\}: \(\d+, \{\}, (?:may|must)-alias\)",
                         header)
    assert len(aliased) == (4 if quantized else 2) * LAYERS, header[:400]
    # as the cache holds it, or as the paged kernel is handed it (a
    # page's 16 x 8 rows dense: the same bytes, so no copy either)
    pool = r"(?:bf16|s8)\[4097,(?:16,8|128),128\]"
    copies = [ln.strip()[:160] for ln in text.split("\n")
              if re.search(rf"= {pool}\S* copy\(", ln)]
    assert not copies, copies
    if program == "decode":
        assert "tpu_custom_call" in text  # the Pallas kernel is in it
    # one pool of K and one of V a layer is all the program holds:
    # arguments alias outputs, and temporaries stay far under one pool
    mem = compiled.memory_analysis()
    one_pool = 4097 * 16 * 8 * 128 * (1 if quantized else 2)
    assert mem.alias_size_in_bytes >= 2 * LAYERS * one_pool
    assert mem.temp_size_in_bytes < one_pool


# -- the block-diffusion programs (ISSUE 28) ----------------------------------

SDAR_LAYERS, SDAR_SLOTS, SDAR_BLOCKS = 2, 64, 8193
SDAR_POOL = (SDAR_BLOCKS, BLOCK, 4, 128)


@pytest.fixture(scope="module")
def sdar():
    """SDAR-30B-A3B's attention geometry (32 query / 4 KV heads of 128 on
    a hidden of 2048) and expert widths (2048 x 768, 8 a token); 16
    experts, not 128, and a small vocabulary: they touch no pool."""
    from paddle_tpu.models import SDAR, SDARConfig

    paddle.seed(0)
    m = SDAR(SDARConfig(vocab_size=1024, num_layers=SDAR_LAYERS, num_experts=16,
                        denoise_steps=2))
    m.eval()
    return m


def _lower_sdar(model, program, one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = [s(SDAR_POOL, jnp.bfloat16)] * SDAR_LAYERS
    arrs = model._param_arrays()
    params = tuple(s(a.shape, jnp.bfloat16) for a in arrs)
    row, scalar = s((PAGES,), jnp.int32), s((), jnp.int32)
    try:
        jitted = model.serving_program(program, mode="pallas")._jitted
        if program == "block_step":
            # the open blocks: ids, mask, opened with, forwards done
            return jitted.lower(
                params, s((SDAR_SLOTS, 2 * 4 + 2), jnp.int32), pools,
                pools, [],
                [], s((SDAR_SLOTS, PAGES), jnp.int32),
                s((SDAR_SLOTS,), jnp.int32), s((SDAR_SLOTS,), jnp.bool_))
        if program == "prefill":
            return jitted.lower(params, s((1, 512), jnp.int64), scalar,
                                row, pools, pools, [], [])
        return jitted.lower(params, s((1, 512), jnp.int64), scalar, scalar,
                            scalar, row, pools, pools, [], [])
    finally:
        model._param_rebind()(arrs)


@pytest.mark.parametrize("program, kernels", [
    ("block_step", 3 * SDAR_LAYERS), ("prefill", 2 * (SDAR_LAYERS - 1)),
    ("extend", 2 * (SDAR_LAYERS - 1))])
def test_the_block_diffusion_programs_alias_every_pool_and_copy_none(
        monkeypatch, one_chip, sdar, program, kernels):
    """At 4 KV heads a scatter of whole [16, 4, 128] pages made the v5e
    compiler re-lay the pool out and copy it twice a layer, the fourth
    device operation of the cell's first traced run (PERF.md, PR 28):
    the prefill writes row by row, as the extend and the step do."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE_COMPILE", "1")
    lowered = _lower_sdar(sdar, program, one_chip)
    if program == "block_step":
        # beside the pools: the packed read-back, the two arrays of the
        # expert layers, and ONE array more since the rule runs in the
        # program (the open blocks after the step), not four: every
        # output costs the host ~50 us a step (PERF.md, PR 28)
        outs = [o.shape for o in jax.tree.leaves(lowered.out_info)]
        assert len(outs) == 4 + 2 * SDAR_LAYERS, outs
        assert outs[1] == (SDAR_SLOTS, 2 * 4 + 2)
    compiled = lowered.compile()
    text = compiled.as_text()
    header = text[:text.index("\n")]
    aliased = re.findall(r"\{\d+\}: \(\d+, \{\}, (?:may|must)-alias\)",
                         header)
    assert len(aliased) == 2 * SDAR_LAYERS, header[:400]
    copies = [ln.strip()[:160] for ln in text.split("\n")
              if re.search(r"= bf16\[8193,(?:16,4|64),128\]\S* copy\(", ln)]
    assert not copies, copies
    # the block attention (the step only) and the two grouped matmuls a
    # layer; a prefill's last layer stops at its keys and values
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    one_pool = 8193 * 16 * 4 * 128 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * SDAR_LAYERS * one_pool
    # the extend's dense attention over the whole paged context holds
    # two [512, 32, 2048] float32 arrays (134 MB each); no pool beside
    assert mem.temp_size_in_bytes < (3 if program == "extend" else 1) \
        * one_pool


# -- the hybrid model's programs (ISSUE 32) -----------------------------------

JAMBA_SLOTS, JAMBA_PAGES = 128, 256
JAMBA_BLOCKS = JAMBA_SLOTS * JAMBA_PAGES + 1
JAMBA_POOL = (JAMBA_BLOCKS, BLOCK, 1, 128)
JAMBA_STATE = (1, JAMBA_SLOTS, 16, 5120)       # [state layers, slots, N, E]
JAMBA_TAIL = (1, 3, JAMBA_SLOTS, 5120)         # [state layers, K-1, slots, E]


@pytest.fixture(scope="module")
def jamba():
    """AI21-Jamba2-3B's mixer and attention geometry (E 5120, N 16, R
    160, 20 query heads on 1 KV head of 128), one layer of each kind;
    the feed-forward and the vocabulary cut down (they touch neither
    pool nor state) so the parameters are 60M, not 3B."""
    from paddle_tpu.models import Jamba, JambaConfig

    paddle.seed(0)
    m = Jamba(JambaConfig(vocab_size=1024, intermediate_size=1024,
                          num_layers=2, attn_layer_period=2,
                          attn_layer_offset=1))
    m.eval()
    return m


def _lower_jamba(model, program, one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = [s(JAMBA_POOL, jnp.bfloat16)]
    state = (s(JAMBA_STATE, jnp.float32), s(JAMBA_TAIL, jnp.bfloat16))
    arrs = model._param_arrays()
    params = tuple(s(a.shape, jnp.bfloat16) for a in arrs)
    key = jax.random.key(0)
    key, temp = s(key.shape, key.dtype), s((), jnp.float32)
    row, scalar = s((JAMBA_PAGES,), jnp.int32), s((), jnp.int32)
    try:
        jitted = model.serving_program(program, mode="pallas")._jitted
        if program == "decode":
            return jitted.lower(
                params, s((JAMBA_SLOTS,), jnp.int32), pools, pools, [], [],
                state, s((JAMBA_SLOTS, JAMBA_PAGES), jnp.int32),
                s((JAMBA_SLOTS,), jnp.int32), s((JAMBA_SLOTS,), jnp.bool_),
                scalar, key, temp)
        if program == "prefill":
            return jitted.lower(params, s((1, 512), jnp.int64), scalar, row,
                                scalar, pools, pools, [], [], state, key,
                                temp)
        return jitted.lower(params, s((1, 512), jnp.int64), scalar, scalar,
                            s((), jnp.bool_), row, scalar, pools, pools, [],
                            [], state, key, temp)
    finally:
        model._param_rebind()(arrs)


@pytest.mark.parametrize("program, kernels", [
    ("decode", 2), ("prefill", 1), ("extend", 1)])
def test_the_hybrid_programs_alias_pools_and_state_and_copy_none(
        monkeypatch, one_chip, jamba, program, kernels):
    """The recurrent state rides the programs beside the pools: the v5e
    compiler pairs both pools and both state arrays with outputs and
    puts no whole-pool or whole-state ``copy`` in front of the paged
    call (20 query rows on one KV head), the state-update kernel (in
    place in the stacked array) or the prefill's row writes."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE_COMPILE", "1")
    compiled = _lower_jamba(jamba, program, one_chip).compile()
    text = compiled.as_text()
    header = text[:text.index("\n")]
    aliased = re.findall(r"\{\d+\}: \(\d+, \{\}, (?:may|must)-alias\)",
                         header)
    assert len(aliased) == 4, header[:400]
    held = (rf"bf16\[{JAMBA_BLOCKS},(?:16,1|16),128\]",
            r"f32\[1,128,16,5120\]", r"bf16\[1,3,128,5120\]")
    copies = [ln.strip()[:160] for ln in text.split("\n")
              if any(re.search(rf"= {shape}\S* copy\(", ln)
                     for shape in held)]
    assert not copies, copies
    # decode: the state update and the paged attention; a prefill: the
    # scan (its attention is the flash kernel on the chip, XLA's here)
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    one_pool = JAMBA_BLOCKS * 16 * 128 * 2
    state = 128 * 16 * 5120 * 4 + 3 * 128 * 5120 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * one_pool + state
    # the extend's dense attention over the whole 4096-token context
    # holds two [512, 20, 4096] float32 arrays (168 MB each)
    assert mem.temp_size_in_bytes < (6 if program == "extend" else 1) \
        * one_pool
