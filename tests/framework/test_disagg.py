"""Disaggregated prefill/decode serving (serving/kv_transfer.py +
serving/disagg.py).

What must hold:

- the KV export/import round trip is BIT-EXACT, fp32 and int8 (data
  and scale rows move together) — the imported pool rows equal the
  source rows to the byte;
- a frame that does not validate is rejected LOUDLY and atomically:
  crc corruption, truncation, bad magic, geometry mismatch, digest
  mismatch — all raise ``TransferError`` with the destination pool
  untouched;
- after import the destination pool is in exactly the state
  ``commit_prefix`` + ``free_slot`` leaves local blocks in: refcount
  0, reclaimable, re-admissible; handoff admission refs them and COW
  protects the shared partial tail;
- the two-stage pipeline's greedy outputs are bit-identical to
  co-located serving (fp32 and int8), a decode replica runs ZERO
  prefill compute, an injected ``disagg.transfer`` fault fails open to
  co-located serving with no lost request, and
  ``FLAGS_serving_disagg=0`` is a byte-for-byte pass-through with
  ``serving.disagg.*`` counter silence.
"""

import pickle

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler import metrics
from paddle_tpu.serving import kv_transfer
from paddle_tpu.serving.disagg import DisaggPipeline
from paddle_tpu.serving.kv_transfer import TransferError
from paddle_tpu.serving.router import NoReplicaAvailable, Router
from paddle_tpu.serving.scheduler import HandoffError
from paddle_tpu.testing import faults

# tiny_llama fixture + the pinned engine config come from conftest.py
# (rootdir-relative import, the test_spec_decode.py convention)
from conftest import tiny_engine  # noqa: E402

PROMPT = list(range(1, 13))  # 12 tokens: one full 8-block + 4 partial


@pytest.fixture()
def disagg_flags():
    saved = paddle.get_flags(["FLAGS_serving_router",
                              "FLAGS_serving_disagg"])
    paddle.set_flags({"FLAGS_serving_router": True,
                      "FLAGS_serving_disagg": True})
    yield
    paddle.set_flags(saved)


def _same_weights_model():
    """A fresh model bit-identical to the session ``tiny_llama`` (same
    seed, same config) — disagg needs several engines with identical
    weights, and engines must not share one cache-carrying model's
    pools across roles in these tests."""
    from paddle_tpu.models import Llama, LlamaConfig

    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


def _prefill_engine(model, **kw):
    eng = tiny_engine(model, prefix_cache=True, role="prefill", **kw)
    h = eng.submit(PROMPT, max_new_tokens=8, prefill_only=True)
    eng.run_until_idle()
    toks = h.result(timeout=30)
    assert len(toks) == 1  # prefill stage stops at the first token
    return eng, toks[0]


def _pool_rows(cache, blocks):
    idx = np.asarray(blocks, np.int64)
    out = []
    for i in range(cache.num_layers):
        out.append((np.asarray(cache.k_pools[i][idx]),
                    np.asarray(cache.v_pools[i][idx])))
    return out


def _resident_blocks(cache, ids):
    plan = cache.plan_prefix(np.asarray(ids, np.int64))
    assert plan.covered_tokens == plan.num_tokens
    blocks = list(plan.matched_blocks)
    if plan.partial_block is not None:
        blocks.append(plan.partial_block)
    return blocks


# -- export/import round trip ----------------------------------------------

def test_roundtrip_fp32_bit_exact(tiny_llama):
    src, _ = _prefill_engine(tiny_llama)
    dst = tiny_engine(_same_weights_model(), prefix_cache=True,
                      role="decode")
    frame, exported = kv_transfer.export_prefix(src.cache, PROMPT)
    assert exported.num_tokens == len(PROMPT)
    assert exported.blocks == 2 and exported.partial_len == 4
    res = kv_transfer.import_prefix(dst.cache, frame)
    assert res.blocks_imported == 2 and res.blocks_deduped == 0
    assert res.nbytes == len(frame) == exported.nbytes
    src_rows = _pool_rows(src.cache, _resident_blocks(src.cache, PROMPT))
    dst_rows = _pool_rows(dst.cache, _resident_blocks(dst.cache, PROMPT))
    for (sk, sv), (dk, dv) in zip(src_rows, dst_rows):
        np.testing.assert_array_equal(sk, dk)
        np.testing.assert_array_equal(sv, dv)


# -- the pools are donated: nobody keeps one across a step (ISSUE 27) -------

@pytest.mark.filterwarnings("error::UserWarning")
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["plain", "int8"])
def test_a_landing_consumes_the_pools_and_counts_as_donated(tiny_llama,
                                                            kv_dtype):
    src, _ = _prefill_engine(tiny_llama, kv_cache_dtype=kv_dtype)
    dst = tiny_engine(_same_weights_model(), prefix_cache=True,
                      role="decode", kv_cache_dtype=kv_dtype)
    frame, _ = kv_transfer.export_prefix(src.cache, PROMPT)
    handed_in = dst.cache.pool_arrays()
    c0 = metrics.snapshot("serving.kv.")
    kv_transfer.import_prefix(dst.cache, frame)
    c1 = metrics.snapshot("serving.kv.")
    assert all(a.is_deleted() for a in handed_in)
    # one block-copy program for every pool, not one scatter a pool
    assert c1["serving.kv.donated_calls"] \
        == c0["serving.kv.donated_calls"] + 1
    assert c1["serving.kv.copied_calls"] == c0["serving.kv.copied_calls"]


def test_export_beside_a_stepping_engine_never_reads_a_deleted_pool(
        tiny_llama):
    """``export_prefix`` runs on the pipeline's thread while the
    prefill replica's own thread steps other requests. Each step
    deletes the pools it is handed; the export holds ``pool_lock`` and
    so reads between two steps — without it this raises "Array has
    been deleted" within a few tries."""
    import threading

    import jax.numpy as jnp

    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(tiny_llama, max_batch=4, block_size=8,
                        max_seq_len=64, bucket_cap=32, temperature=0.0,
                        dtype=jnp.float32, prefix_cache=True,
                        background=True)
    eng.submit(PROMPT, max_new_tokens=1).result(timeout=60)
    want, _ = kv_transfer.export_prefix(eng.cache, PROMPT)
    errors, done = [], threading.Event()

    def exporter():
        try:
            while not done.is_set():
                frame, _ = kv_transfer.export_prefix(eng.cache, PROMPT)
                assert frame == want
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    t = threading.Thread(target=exporter)
    t.start()
    try:
        rng = np.random.default_rng(3)
        for _ in range(3):
            hs = [eng.submit(rng.integers(20, 250, size=9),
                             max_new_tokens=12) for _ in range(3)]
            for h in hs:
                assert len(h.result(timeout=120)) == 12
    finally:
        done.set()
        t.join(timeout=60)
        eng.close()
    assert not errors, errors


def test_roundtrip_int8_data_and_scales_move_together(tiny_llama):
    src, _ = _prefill_engine(_same_weights_model(),
                             kv_cache_dtype="int8")
    dst = tiny_engine(_same_weights_model(), prefix_cache=True,
                      role="decode", kv_cache_dtype="int8")
    frame, _ = kv_transfer.export_prefix(src.cache, PROMPT)
    kv_transfer.import_prefix(dst.cache, frame)
    sb = _resident_blocks(src.cache, PROMPT)
    db = _resident_blocks(dst.cache, PROMPT)
    si, di = np.asarray(sb, np.int64), np.asarray(db, np.int64)
    for i in range(src.cache.num_layers):
        np.testing.assert_array_equal(
            np.asarray(src.cache.k_pools[i][si]),
            np.asarray(dst.cache.k_pools[i][di]))
        np.testing.assert_array_equal(
            np.asarray(src.cache.v_pools[i][si]),
            np.asarray(dst.cache.v_pools[i][di]))
        # the int8 rows are meaningless without their float32 scales:
        # the pair must cross the wire together, bit-exact
        np.testing.assert_array_equal(
            np.asarray(src.cache.k_scales[i][si]),
            np.asarray(dst.cache.k_scales[i][di]))
        np.testing.assert_array_equal(
            np.asarray(src.cache.v_scales[i][si]),
            np.asarray(dst.cache.v_scales[i][di]))


def test_import_dedup_first_registration_wins(tiny_llama):
    src, _ = _prefill_engine(tiny_llama)
    dst = tiny_engine(_same_weights_model(), prefix_cache=True)
    frame, _ = kv_transfer.export_prefix(src.cache, PROMPT)
    first = kv_transfer.import_prefix(dst.cache, frame)
    blocks_before = _resident_blocks(dst.cache, PROMPT)
    again = kv_transfer.import_prefix(dst.cache, frame)
    assert first.blocks_imported == 2
    assert again.blocks_imported == 0 and again.blocks_deduped == 2
    assert _resident_blocks(dst.cache, PROMPT) == blocks_before


def test_export_requires_resident_prefix(tiny_llama):
    eng = tiny_engine(tiny_llama, prefix_cache=True)
    with pytest.raises(TransferError, match="not fully resident"):
        kv_transfer.export_prefix(eng.cache, [91, 92, 93, 94, 95])


# -- frame validation (all-or-nothing) -------------------------------------

def _corruption_free_state(cache):
    return (cache.num_free_blocks(), len(cache._prefix_index),
            len(cache._partial_index))


def test_crc_corruption_quarantined(tiny_llama):
    src, _ = _prefill_engine(tiny_llama)
    dst = tiny_engine(_same_weights_model(), prefix_cache=True)
    frame, _ = kv_transfer.export_prefix(src.cache, PROMPT)
    before = _corruption_free_state(dst.cache)
    bad = bytearray(frame)
    bad[len(frame) // 2] ^= 0xFF  # one flipped payload byte
    with pytest.raises(TransferError, match="crc mismatch"):
        kv_transfer.import_prefix(dst.cache, bytes(bad))
    assert _corruption_free_state(dst.cache) == before


def test_truncated_and_bad_magic_rejected(tiny_llama):
    src, _ = _prefill_engine(tiny_llama)
    dst = tiny_engine(_same_weights_model(), prefix_cache=True)
    frame, _ = kv_transfer.export_prefix(src.cache, PROMPT)
    with pytest.raises(TransferError, match="short frame"):
        kv_transfer.unpack_frame(frame[:4])
    with pytest.raises(TransferError, match="bad magic"):
        kv_transfer.unpack_frame(b"NOTMAGIC" + frame[8:])
    with pytest.raises(TransferError, match="length mismatch"):
        kv_transfer.import_prefix(dst.cache, frame[:-3])


def test_digest_mismatch_rejected_loudly(tiny_llama):
    src, _ = _prefill_engine(tiny_llama)
    dst = tiny_engine(_same_weights_model(), prefix_cache=True)
    frame, _ = kv_transfer.export_prefix(src.cache, PROMPT)
    obj = pickle.loads(kv_transfer.unpack_frame(frame))
    obj["ids"] = np.asarray([7] + PROMPT[1:], np.int64)  # re-keyed ids
    forged = kv_transfer.pack_frame(pickle.dumps(obj, protocol=4))
    before = _corruption_free_state(dst.cache)
    with pytest.raises(TransferError, match="digest mismatch"):
        kv_transfer.import_prefix(dst.cache, forged)
    assert _corruption_free_state(dst.cache) == before


def test_geometry_mismatch_rejected(tiny_llama):
    src, _ = _prefill_engine(tiny_llama)
    dst16 = tiny_engine(_same_weights_model(), prefix_cache=True,
                        block_size=16)
    frame, _ = kv_transfer.export_prefix(src.cache, PROMPT)
    with pytest.raises(TransferError, match="geometry mismatch"):
        kv_transfer.import_prefix(dst16.cache, frame)
    # fp32 frame into an int8 pool must refuse too (dtype is geometry)
    dst_q = tiny_engine(_same_weights_model(), prefix_cache=True,
                        kv_cache_dtype="int8")
    with pytest.raises(TransferError, match="geometry mismatch"):
        kv_transfer.import_prefix(dst_q.cache, frame)


# -- pool state after import / handoff admission ---------------------------

def test_imported_blocks_park_refcount_zero_reclaimable(tiny_llama):
    src, _ = _prefill_engine(tiny_llama)
    dst = tiny_engine(_same_weights_model(), prefix_cache=True)
    free_before = dst.cache.num_free_blocks()
    frame, _ = kv_transfer.export_prefix(src.cache, PROMPT)
    kv_transfer.import_prefix(dst.cache, frame)
    blocks = _resident_blocks(dst.cache, PROMPT)
    for b in blocks:
        assert dst.cache._refcount[b] == 0
        assert b in dst.cache._cached_free
    # reclaimable blocks still count as allocatable headroom
    assert dst.cache.num_free_blocks() == free_before


def test_handoff_refcount_and_cow(tiny_llama):
    src, first = _prefill_engine(tiny_llama)
    dst = tiny_engine(_same_weights_model(), prefix_cache=True)
    frame, _ = kv_transfer.export_prefix(src.cache, PROMPT)
    kv_transfer.import_prefix(dst.cache, frame)
    full_b, part_b = _resident_blocks(dst.cache, PROMPT)
    # two concurrent handoffs off the same imported prefix: the full
    # block is shared (refcount 2), the partial tail COWs per request
    h1 = dst.submit_handoff(PROMPT, first, max_new_tokens=4)
    h2 = dst.submit_handoff(PROMPT, first, max_new_tokens=4)
    assert dst.cache._refcount[full_b] == 2
    assert dst.cache._refcount[part_b] >= 1
    dst.run_until_idle()
    assert h1.result(timeout=30) == h2.result(timeout=30)
    # both finished: shared blocks parked again, nothing leaked
    assert dst.cache._refcount[full_b] == 0
    assert dst.cache._refcount[part_b] == 0


def test_handoff_rejects_uncovered_prompt(tiny_llama):
    dst = tiny_engine(tiny_llama, prefix_cache=True)
    with pytest.raises(HandoffError, match="covers 0/12"):
        dst.scheduler.admit_handoff(PROMPT, 3, max_new_tokens=4)


def test_prefill_only_requires_prefix_cache(tiny_llama):
    eng = tiny_engine(tiny_llama, prefix_cache=False)
    with pytest.raises(ValueError, match="requires the prefix cache"):
        eng.submit(PROMPT, max_new_tokens=4, prefill_only=True)


# -- the two-stage pipeline ------------------------------------------------

def _pipeline(prefill_kw=None, decode_kw=None):
    pre = tiny_engine(_same_weights_model(), prefix_cache=True,
                      role="prefill", **(prefill_kw or {}))
    dec = tiny_engine(_same_weights_model(), prefix_cache=True,
                      role="decode", **(decode_kw or {}))
    r = Router()
    r.add_replica("pre", engine=pre)
    r.add_replica("dec", engine=dec)
    return DisaggPipeline(r), pre, dec


def _reference(prompt, max_new, **kw):
    ref = tiny_engine(_same_weights_model(), prefix_cache=True, **kw)
    h = ref.submit(prompt, max_new_tokens=max_new)
    ref.run_until_idle()
    return h.result(timeout=30)


def _disagg_counters():
    snap = metrics.snapshot()
    return {k: snap.get(k, 0) for k in
            ("serving.disagg.handoffs", "serving.disagg.transfer_bytes",
             "serving.disagg.transfer_us", "serving.disagg.fallbacks")}


@pytest.mark.usefixtures("disagg_flags")
def test_pipeline_bit_identical_to_colocated():
    pipe, _, dec = _pipeline()
    before = _disagg_counters()
    h = pipe.submit(PROMPT, max_new_tokens=8)
    pipe.run_until_idle()
    assert h.result(timeout=30) == _reference(PROMPT, 8)
    assert h.status == "DONE"
    after = _disagg_counters()
    assert after["serving.disagg.handoffs"] == \
        before["serving.disagg.handoffs"] + 1
    assert after["serving.disagg.transfer_bytes"] > \
        before["serving.disagg.transfer_bytes"]
    # per-stage billing: the decode replica carried zero prefill
    # tokens and the fabric hop rode the CostReport
    c = h.cost()
    assert c.tokens_prefilled == 0
    assert c.transfer_bytes > 0


@pytest.mark.usefixtures("disagg_flags")
def test_pipeline_int8_bit_identical():
    pipe, _, _ = _pipeline(prefill_kw={"kv_cache_dtype": "int8"},
                           decode_kw={"kv_cache_dtype": "int8"})
    h = pipe.submit(PROMPT, max_new_tokens=8)
    pipe.run_until_idle()
    assert h.result(timeout=30) == _reference(PROMPT, 8,
                                              kv_cache_dtype="int8")


@pytest.mark.usefixtures("disagg_flags")
def test_transfer_fault_fails_open_zero_lost(tiny_llama):
    pipe, _, _ = _pipeline()
    before = _disagg_counters()
    with faults.inject("disagg.transfer", nth=1, count=100):
        h = pipe.submit(PROMPT, max_new_tokens=8)
        pipe.run_until_idle()
        toks = h.result(timeout=30)
    assert h.status == "DONE"  # the request survived the broken fabric
    assert toks == _reference(PROMPT, 8)
    after = _disagg_counters()
    assert after["serving.disagg.fallbacks"] == \
        before["serving.disagg.fallbacks"] + 1
    assert after["serving.disagg.handoffs"] == \
        before["serving.disagg.handoffs"]


@pytest.mark.usefixtures("disagg_flags")
def test_no_decode_replica_falls_back_colocated():
    pre = tiny_engine(_same_weights_model(), prefix_cache=True,
                      role="prefill")
    r = Router()
    r.add_replica("pre", engine=pre)
    pipe = DisaggPipeline(r)
    before = _disagg_counters()
    h = pipe.submit(PROMPT, max_new_tokens=8)
    pre.run_until_idle()
    assert h.result(timeout=30) == _reference(PROMPT, 8)
    assert _disagg_counters()["serving.disagg.fallbacks"] == \
        before["serving.disagg.fallbacks"] + 1


@pytest.mark.usefixtures("disagg_flags")
def test_single_mixed_replica_served_colocated_not_fallback():
    """One mixed-role replica resolves both stages to itself: the
    pipeline skips the two-stage attempt entirely (a self-handoff could
    only fail) and counts colocated — NOT fallbacks, since nothing
    failed."""
    eng = tiny_engine(_same_weights_model(), prefix_cache=True)
    r = Router()
    r.add_replica("solo", engine=eng)
    pipe = DisaggPipeline(r)
    before = _disagg_counters()
    cbefore = metrics.snapshot().get("serving.disagg.colocated", 0)
    h = pipe.submit(PROMPT, max_new_tokens=8)
    pipe.run_until_idle()
    assert h.result(timeout=30) == _reference(PROMPT, 8)
    after = _disagg_counters()
    assert after["serving.disagg.fallbacks"] == \
        before["serving.disagg.fallbacks"]
    assert after["serving.disagg.handoffs"] == \
        before["serving.disagg.handoffs"]
    assert metrics.snapshot().get("serving.disagg.colocated", 0) == \
        cbefore + 1


@pytest.mark.usefixtures("disagg_flags")
def test_prefill_stage_starved_reports_stage_reason():
    dec = tiny_engine(_same_weights_model(), prefix_cache=True,
                      role="decode")
    r = Router()
    r.add_replica("dec", engine=dec)
    pipe = DisaggPipeline(r)
    with pytest.raises(NoReplicaAvailable) as ei:
        pipe.submit(PROMPT, max_new_tokens=8)
    assert "no-prefill-replica" in ei.value.reasons
    assert ei.value.reasons["dec"] == "WrongRole(decode)"


def test_flag_off_passthrough_and_counter_silence():
    saved = paddle.get_flags(["FLAGS_serving_router",
                              "FLAGS_serving_disagg"])
    paddle.set_flags({"FLAGS_serving_router": True,
                      "FLAGS_serving_disagg": False})
    try:
        pipe, pre, dec = _pipeline()
        before = _disagg_counters()
        h = pipe.submit(PROMPT, max_new_tokens=8)
        pipe.run_until_idle()
        toks = h.result(timeout=30)
        assert toks == _reference(PROMPT, 8)
        assert _disagg_counters() == before  # byte-for-byte silence
        # disarmed = a plain Router.submit: the armed router's routed
        # handle, no disagg machinery in the path
        assert hasattr(h, "replica_id")
    finally:
        paddle.set_flags(saved)


# -- role plumbing ---------------------------------------------------------

def test_router_replica_role_resolution(tiny_llama):
    from paddle_tpu.serving.router import RouterReplica

    eng = tiny_engine(tiny_llama, role="decode")
    assert RouterReplica("a").role == "mixed"
    assert RouterReplica("b", engine=eng).role == "decode"
    assert RouterReplica("c", engine=eng, role="prefill").role == \
        "prefill"
    rep = RouterReplica("d", member={"role": "prefill"})
    assert rep.role == "prefill"
    rep.member = {}  # pre-role payload: backward-compatible default
    assert rep.role == "mixed"


def test_registrar_payload_carries_role():
    from paddle_tpu.profiler.fleet import Registrar

    reg = Registrar(store=None, url="http://x", replica_id="r0",
                    role="prefill")
    assert reg._payload()["role"] == "prefill"
    assert Registrar(store=None, url="http://x",
                     replica_id="r1")._payload()["role"] == "mixed"


def test_engine_role_validation(tiny_llama):
    with pytest.raises(ValueError, match="unknown role"):
        tiny_engine(tiny_llama, role="shard")
