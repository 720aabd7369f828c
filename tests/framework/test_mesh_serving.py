"""Mesh-sharded serving pins (ISSUE 15; docs/SERVING.md "Mesh-sharded
serving").

The pytest harness forces 8 host devices (tests/conftest.py), so the
``(data, model)`` serving mesh runs IN-PROCESS here: greedy
1x1-vs-sharded bit-equivalence, prefix-cache hits and preemption under
sharding, per-slice occupancy closure, AOT fingerprint separation
across mesh shapes, and the flag-off byte-for-byte revert with
``serving.mesh.*`` counter silence. The decode attention of a
model-sharded mesh runs under an explicit ``jax.shard_map``
(``ServingMesh.shard_map_armed``). tools/mesh_gate.py re-proves the
corpus cross-process.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import MeshAxisError, init_mesh
from paddle_tpu.profiler import metrics
from paddle_tpu.serving.mesh import (ServingMesh, parse_mesh_spec,
                                     resolve_serving_mesh)


def _model():
    from paddle_tpu.models import Llama, LlamaConfig

    paddle.seed(0)
    m = Llama(LlamaConfig.tiny_tp())
    m.eval()
    return m


def _serve(mesh, prompts, max_new=8, num_blocks=None, max_seq_len=64,
           paged_kernel=None):
    import jax.numpy as jnp

    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(_model(), max_batch=4, block_size=8,
                        max_seq_len=max_seq_len, temperature=0.0,
                        bucket_cap=32, background=False,
                        dtype=jnp.float32, mesh=mesh,
                        num_blocks=num_blocks, paged_kernel=paged_kernel)
    s0 = metrics.snapshot("serving.")
    hs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_idle()
    s1 = metrics.snapshot("serving.")
    outs = [h.tokens() for h in hs]
    eng.close()

    def d(k):
        return (s1.get(k, 0) or 0) - (s0.get(k, 0) or 0)

    return outs, d


def _mixed(seed=7, sizes=(9, 5, 14)):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, size=s) for s in sizes]


@pytest.fixture(scope="module")
def mixed_base():
    """The single-device greedy reference for the shared mixed corpus
    — computed ONCE (engine builds dominate this file's runtime) and
    reused by every equivalence test that serves the same corpus."""
    outs, _ = _serve(None, _mixed())
    return outs


# -- mesh construction + validation ----------------------------------------

def test_parse_mesh_spec():
    assert parse_mesh_spec("1x8") == (1, 8)
    assert parse_mesh_spec("2x4") == (2, 4)
    assert parse_mesh_spec("") == (1, 1)
    assert parse_mesh_spec(None) == (1, 1)
    assert resolve_serving_mesh("1x1") is None
    assert resolve_serving_mesh("") is None
    with pytest.raises(ValueError):
        parse_mesh_spec("8")
    with pytest.raises(ValueError):
        parse_mesh_spec("2x0")
    with pytest.raises(ValueError):
        parse_mesh_spec("axb")


def test_mesh_axis_validation_is_structured():
    # 3 does not divide 8 visible devices: the error names the axis
    with pytest.raises(MeshAxisError) as ei:
        ServingMesh(3, 2)
    assert ei.value.axis == "data"
    assert ei.value.size == 3
    assert ei.value.device_count == 8
    # init_mesh (the training-side entry) raises the same structured
    # error instead of failing deep inside jax Mesh construction
    with pytest.raises(MeshAxisError) as ei:
        init_mesh((5, 2), ["dp", "mp"])
    assert ei.value.axis == "dp"
    # -1 inference still works and validates the result
    m = init_mesh((-1, 2), ["dp", "mp"])
    assert m.shape == [4, 2]
    # the model axis must divide the head extents (tiny() has 2 kv
    # heads: an 8-way model axis is structurally impossible)
    from paddle_tpu.models import Llama, LlamaConfig

    paddle.seed(0)
    tiny = Llama(LlamaConfig.tiny())
    with pytest.raises(MeshAxisError) as ei:
        tiny.apply_serving_mesh(ServingMesh(1, 8))
    assert ei.value.axis == "model"


# -- greedy bit-equivalence ------------------------------------------------

def test_mesh_serving_greedy_matches_1x1(mixed_base):
    """The core mesh pin: a 1x8 tensor-parallel serve (params sharded
    by head/hidden, KV pool by kv-head, decode attention under
    jax.shard_map) emits the same greedy tokens as the single-device
    run."""
    shard, _ = _serve("1x8", _mixed())
    assert shard == mixed_base
    # armed engines move the mesh gauges
    assert metrics.snapshot("serving.mesh.")["serving.mesh.devices"] == 8


def test_sharded_greedy_bit_equivalence_pallas_per_shard(mixed_base):
    """The route the chip takes: each shard of the shard_map decode
    attention runs the Pallas kernel on its local kv-heads (interpret
    mode here) — same greedy bit-equivalence contract."""
    assert ServingMesh(1, 8).shard_map_armed
    shard, d = _serve("1x8", _mixed(), paged_kernel="pallas")
    assert shard == mixed_base
    assert d("serving.kernel.pallas") > 0
    assert d("serving.kernel.dense") == 0


# The three tests below each build two full engines (the dominant cost
# of this file); they are `slow`-marked so the 870s tier-1 window keeps
# its tail — tools/mesh_gate.py re-proves all three cross-process on
# every pre-commit run (shared-prefix counters, forced preemption, and
# the warm-AOT zero-compile boot are its checks 1 and 2).

@pytest.mark.slow
def test_prefix_cache_hits_under_sharding():
    rng = np.random.default_rng(7)
    sysp = rng.integers(3, 250, size=17)
    prompts = [np.concatenate([sysp, rng.integers(3, 250, size=4)])
               for _ in range(4)]
    base, db = _serve(None, prompts)
    shard, ds = _serve("1x8", prompts)
    assert shard == base
    assert db("serving.prefix.hit_blocks") > 0
    assert ds("serving.prefix.hit_blocks") == \
        db("serving.prefix.hit_blocks")
    assert ds("serving.prefix.cow_copies") == \
        db("serving.prefix.cow_copies")


@pytest.mark.slow
def test_preemption_under_sharding():
    prompts = [np.random.default_rng(5).integers(3, 250, size=9)
               for _ in range(4)]
    base, db = _serve(None, prompts, max_new=24, num_blocks=13)
    shard, ds = _serve("1x8", prompts, max_new=24, num_blocks=13)
    assert shard == base
    assert db("serving.preempt") > 0
    assert ds("serving.preempt") == db("serving.preempt")


# -- per-slice capacity ----------------------------------------------------

def test_per_slice_occupancy_sums_to_aggregate():
    import jax.numpy as jnp

    from paddle_tpu.inference.paged import PagedKVCache

    cache = PagedKVCache(1, 2, 4, num_blocks=17, block_size=4,
                         max_blocks_per_seq=4, max_batch=4,
                         dtype=jnp.float32, num_slices=2)
    # two live slots in different slices
    ids = np.arange(8)
    plan = cache.plan_prefix(ids)
    s1 = cache.alloc_slot_cached(plan)
    cache.seq_lens[s1] = 8
    cache.commit_prefix(s1, plan)
    s2 = cache.alloc_slot(10)
    assert s2 is not None
    # a freed registered slot parks cached_free
    cache.free_slot(s1)
    agg = cache.occupancy()
    slices = cache.occupancy_slices()
    assert len(slices) == 2
    for key in agg:
        assert sum(s[key] for s in slices) == agg[key], key
    for s in slices:
        assert s["active"] + s["cached_free"] + s["free"] == s["usable"]
    assert agg["cached_free"] > 0
    # per-slice pool bytes are proportional shares of the aggregate
    assert sum(cache.pool_bytes(slice=i) for i in range(2)) <= \
        cache.pool_bytes()
    assert cache.pool_bytes(slice=0) > 0
    # the binding slice is the one with the most allocatable blocks
    bs = cache.binding_slice()
    assert bs in (0, 1)
    assert cache.num_free_blocks(bs) == max(
        cache.num_free_blocks(0), cache.num_free_blocks(1))
    # unsliced caches keep aggregate semantics (None = pre-mesh)
    flat = PagedKVCache(1, 2, 4, num_blocks=9, block_size=4,
                        max_blocks_per_seq=4, max_batch=2,
                        dtype=jnp.float32)
    assert flat.binding_slice() is None
    assert flat.occupancy(slice=None) == flat.occupancy()


def test_slice_allocation_stays_in_slice():
    import jax.numpy as jnp

    from paddle_tpu.inference.paged import PagedKVCache

    cache = PagedKVCache(1, 2, 4, num_blocks=17, block_size=4,
                         max_blocks_per_seq=4, max_batch=4,
                         dtype=jnp.float32, num_slices=2)
    slot = cache.alloc_slot(8)
    sl = cache.slice_of_slot(slot)
    for b in cache._slot_blocks[slot]:
        assert cache._slice_of_block(b) == sl
    # growth draws from the slot's slice too
    cache.seq_lens[slot] = 8
    assert cache.ensure_capacity(slot, 9)
    for b in cache._slot_blocks[slot]:
        assert cache._slice_of_block(b) == sl


# -- AOT cache fingerprinting ----------------------------------------------

def test_aot_fingerprint_differs_across_mesh_shapes():
    from paddle_tpu.serving import aot_cache

    m = _model()
    assert m._aot_tag("llama.paged_decode") == "llama.paged_decode"
    m.paged_programs["decode", False, "auto"] = object()  # built
    m.apply_serving_mesh(ServingMesh(1, 2))
    # mesh application empties the one dict of serving programs so they
    # re-lower sharded
    assert m.paged_programs == {}
    t12 = m._aot_tag("llama.paged_decode")
    assert t12 == "llama.paged_decode.mesh1x2"
    m.paged_programs["decode", False, "auto"] = object()
    m.paged_programs["prefill", True, None] = object()
    m.apply_serving_mesh(ServingMesh(2, 4))
    assert m.paged_programs == {}
    t24 = m._aot_tag("llama.paged_decode")
    assert t24 == "llama.paged_decode.mesh2x4"
    # even on identical lowered text the store entries stay disjoint
    text = "module @jit_fn { }"
    fps = {aot_cache.fingerprint(t, text)
           for t in ("llama.paged_decode", t12, t24)}
    assert len(fps) == 3


@pytest.mark.slow
def test_warmup_sharded_zero_recompile():
    import jax.numpy as jnp

    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(_model(), max_batch=4, block_size=8,
                        max_seq_len=32, temperature=0.0, bucket_cap=32,
                        background=False, dtype=jnp.float32, mesh="1x2",
                        ready=False)
    eng.warmup()
    c0 = metrics.snapshot("xla.").get("xla.compile.count", 0)
    h = eng.submit(np.random.default_rng(3).integers(3, 250, size=9),
                   max_new_tokens=6)
    eng.run_until_idle()
    c1 = metrics.snapshot("xla.").get("xla.compile.count", 0)
    assert len(h.tokens()) == 6
    assert c1 - c0 == 0  # the sharded bucket set was fully warmed
    eng.close()


# -- flag routing + disarmed revert ----------------------------------------

def test_mesh_flag_routing(mixed_base):
    from paddle_tpu.core import flags as flags_mod

    e0 = metrics.snapshot("serving.mesh.")["serving.mesh.engines"]
    try:
        flags_mod.set_flags({"FLAGS_serving_mesh": "1x2"})
        outs, _ = _serve(None, _mixed())  # mesh=None -> reads the flag
    finally:
        flags_mod.set_flags({"FLAGS_serving_mesh": ""})
    snap = metrics.snapshot("serving.mesh.")
    assert snap["serving.mesh.engines"] == e0 + 1
    assert snap["serving.mesh.model_shards"] == 2
    assert snap["serving.mesh.data_slices"] == 1
    assert outs == mixed_base


def test_flag_off_revert_and_counter_silence(mixed_base):
    """FLAGS_serving_mesh unset (the module baseline) and an explicit
    '1x1' route through the identical disarmed code: same outputs,
    zero serving.mesh.* movement, zero movement on any slice-labeled
    gauge."""
    m0 = metrics.snapshot("serving.mesh.")
    k0 = {k: v for k, v in metrics.snapshot("serving.kv.").items()
          if '{slice="' in k}
    one, _ = _serve("1x1", _mixed())
    assert one == mixed_base
    assert metrics.snapshot("serving.mesh.") == m0
    k1 = {k: v for k, v in metrics.snapshot("serving.kv.").items()
          if '{slice="' in k}
    assert k1 == k0  # disarmed runs never touch slice series


# -- labeled-series plumbing (exposition + fleet federation) ---------------

def test_labeled_gauge_roundtrip_and_fleet_labeling():
    from paddle_tpu.profiler import export, fleet

    metrics.gauge("meshtest.sliced", labels={"slice": "3"}).set(7)
    metrics.gauge("meshtest.plain").set(2)
    text = export.render_prometheus(prefix="meshtest.")
    parsed = export.parse_prometheus(text)
    key = 'meshtest_sliced{slice="3"}'
    assert parsed[key]["labels"] == {"slice": "3"}
    assert parsed[key]["value"] == 7
    assert parsed["meshtest_plain"]["value"] == 2
    # fleet federation: slice-labeled series gain replica_id BESIDE
    # their own labels (two replicas' slice series must not collide)
    labeled = fleet.label_replica(parsed, "r9")
    k2 = 'meshtest_sliced{replica_id="r9",slice="3"}'
    assert k2 in labeled
    assert labeled[k2]["labels"] == {"slice": "3", "replica_id": "r9"}
    # ...and merge_scrapes keeps them out of the fleet aggregate,
    # exactly like replica-labeled series
    merged = fleet.merge_scrapes({"r1": parsed, "r2": parsed})
    assert key not in merged
    assert merged["meshtest_plain"]["value"] == 4


def test_capacity_view_renders_slices():
    from paddle_tpu.profiler import _capacity_view

    snap = {"serving.steps": 5, "accounting.steps": 5,
            "serving.kv.active_blocks": 6, "serving.kv.free_blocks": 2,
            "serving.kv.shared_blocks": 1, "serving.kv.cached_blocks": 0,
            'serving.kv.active_blocks{slice="0"}': 4,
            'serving.kv.free_blocks{slice="0"}': 1,
            'serving.kv.active_blocks{slice="1"}': 2,
            'serving.kv.free_blocks{slice="1"}': 1}
    text = "\n".join(_capacity_view(snap))
    assert "kv.slice[0]" in text
    assert "kv.slice[1]" in text


# -- sharded pools are written in place too (ISSUE 27) ---------------------

@pytest.mark.filterwarnings("error::UserWarning")  # "Some donated
# buffers were not usable": an output sharded otherwise than its input
@pytest.mark.parametrize("spec, kv_dtype", [
    ("1x2", None), ("2x4", None), ("1x2", "int8")],
    ids=["mesh-1x2", "mesh-2x4", "mesh-1x2-int8"])
def test_sharded_programs_take_the_pools_donated(mixed_base, spec,
                                                 kv_dtype):
    """Prefill and decode on the mesh: every program consumes the pools
    it is handed, what comes back is sharded as what went in, every call
    counts as donated and none as copied, and the greedy tokens are the
    single-device ones."""
    import jax.numpy as jnp

    from conftest import assert_lowered_donates
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(_model(), max_batch=4, block_size=8,
                        max_seq_len=64, temperature=0.0, bucket_cap=32,
                        background=False, dtype=jnp.float32, mesh=spec,
                        kv_cache_dtype=kv_dtype)
    cache, model = eng.cache, eng.scheduler.model
    handed_in = cache.pool_arrays()
    shardings = [a.sharding for a in handed_in]
    assert len(shardings[0].device_set) == eng.scheduler.mesh.devices
    c0 = metrics.snapshot("serving.kv.")
    hs = [eng.submit(p, max_new_tokens=8) for p in _mixed()]
    eng.run_until_idle()
    c1 = metrics.snapshot("serving.kv.")
    assert all(a.is_deleted() for a in handed_in)
    for a, want in zip(cache.pool_arrays(), shardings):
        assert a.sharding.is_equivalent_to(want, a.ndim)
    assert c1["serving.kv.donated_calls"] \
        - c0["serving.kv.donated_calls"] >= 3 + 7  # prefills + decodes
    assert c1["serving.kv.copied_calls"] == c0["serving.kv.copied_calls"]
    if kv_dtype is None:
        assert [h.tokens() for h in hs] == mixed_base

    # the sharded decode program, lowered: pools donated, nothing else
    import jax

    quantized = kv_dtype is not None
    assert ("decode", quantized, "auto") in model.paged_programs
    program, args = model.paged_call_args(
        cache, "decode", (jnp.zeros((4,), jnp.int32),),
        (cache.block_tables, jnp.asarray(cache.seq_lens),
         jnp.ones((4,), bool), jax.random.key(0), jnp.float32(0.0)),
        mode="auto")
    try:
        lowered = program._jitted.lower(*args)
    finally:
        model._param_rebind()(args[0])
    assert_lowered_donates(lowered, (2, 3, 4, 5) if quantized else (2, 3))
    eng.close()
