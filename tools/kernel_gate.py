"""Pallas serving-kernel gate (docs/PERF.md "Pallas serving-kernel
tier"): the FLAGS_paged_kernel routing contract through four pass/fail
checks, in order of importance:

  1. equivalence — engines serving a mixed corpus (ragged lengths,
     shared prefixes) over the Pallas route (FLAGS_paged_kernel=pallas,
     interpret mode on CPU) emit BIT-IDENTICAL tokens to the dense
     reference route, for full-precision AND int8 KV pools, and
     repeat-run deterministically;
  2. routing counters — the pallas serve moves serving.kernel.pallas
     (and .interpret on CPU) at its decode trace; the dense-route
     counter stays untouched by the pallas serve;
  3. warmup zero-recompile — a warmed engine with the kernel routed in
     serves its first request without a single new XLA compile
     (``xla.compile.count`` delta == 0), i.e. the kernel tier rides the
     existing AOT warmup ladder;
  4. forced-off — FLAGS_paged_kernel=dense is a byte-for-byte revert
     with total serving.kernel.* counter silence.

Exit 0 on pass, 1 on fail; one line per check. Runs under
JAX_PLATFORMS=cpu (tier-1, like tests/framework/test_pallas_kernels.py
which pins the same contract as pytest); wired into tools/suite_gate.py
beside the serving gates, and appends a ``kernel_gate`` entry (check
bits + corpus size) to the continuous-bench ledger
(tools/bench_ledger.py).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# the mixed corpus: ragged lengths around block (8) and bucket
# boundaries plus a shared prefix pair — the shapes that stress the
# in-kernel gather masks
CORPUS = [
    [3, 17, 9, 42, 7],
    [5, 5, 5, 5, 5, 5, 5, 5],            # exact block
    [11, 2, 9],
    [3, 17, 9, 42, 7, 100, 101, 102, 103, 104, 105],
    [3, 17, 9, 42, 7, 200],              # shared prefix with [0]
]
MAX_NEW = 8


def _model():
    import paddle_tpu as paddle
    from paddle_tpu.models import Llama, LlamaConfig

    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


def _engine(model, **kw):
    # the same pinned config as tests/framework/conftest.py tiny_engine
    import jax.numpy as jnp

    from paddle_tpu.serving import ServingEngine

    kw.setdefault("max_batch", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("bucket_cap", 32)
    return ServingEngine(model, temperature=0.0, background=False,
                         dtype=jnp.float32, **kw)


def _serve(model, **kw):
    eng = _engine(model, **kw)
    hs = [eng.submit(p, max_new_tokens=MAX_NEW) for p in CORPUS]
    eng.run_until_idle()
    out = [h.result(timeout=60) for h in hs]
    eng.close()
    return out


def _kern_counters():
    from paddle_tpu.profiler import metrics

    snap = metrics.snapshot("serving.kernel")
    return {k: snap.get(k, 0) for k in
            ("serving.kernel.pallas", "serving.kernel.dense",
             "serving.kernel.interpret")}


def check_equivalence(model):
    ok = True
    for label, kw in (("fp32", {}), ("int8", {"kv_cache_dtype": "int8"})):
        dense = _serve(model, paged_kernel="dense", **kw)
        pallas = _serve(model, paged_kernel="pallas", **kw)
        again = _serve(model, paged_kernel="pallas", **kw)
        same = pallas == dense
        det = pallas == again
        ok = ok and same and det
        print(f"[kernel-gate] equivalence[{label}]: "
              f"pallas==dense={same} deterministic={det} "
              f"{'PASS' if same and det else 'FAIL'}")
    return ok


def check_counters(model):
    # counters move at trace time: drop the cached decode programs so
    # the serve retraces and the movement is observable
    model.paged_programs.clear()
    before = _kern_counters()
    _serve(model, paged_kernel="pallas", kv_cache_dtype="int8")
    after = _kern_counters()
    moved = after["serving.kernel.pallas"] > \
        before["serving.kernel.pallas"]
    import jax
    if jax.default_backend() == "cpu":
        moved = moved and after["serving.kernel.interpret"] > \
            before["serving.kernel.interpret"]
    dense_still = after["serving.kernel.dense"] == \
        before["serving.kernel.dense"]
    ok = moved and dense_still
    print(f"[kernel-gate] counters: pallas-moved={moved} "
          f"dense-untouched={dense_still} {'PASS' if ok else 'FAIL'}")
    return ok


def check_warmup_zero_recompile(model):
    from paddle_tpu.profiler import metrics

    eng = _engine(model, paged_kernel="pallas", kv_cache_dtype="int8")
    eng.warmup()
    c0 = metrics.snapshot().get("xla.compile.count", 0)
    h = eng.submit(CORPUS[0], max_new_tokens=MAX_NEW)
    eng.run_until_idle()
    h.result(timeout=60)
    eng.close()
    compiles = metrics.snapshot().get("xla.compile.count", 0) - c0
    ok = compiles == 0
    print(f"[kernel-gate] warmup: request_compiles={compiles} "
          f"{'PASS' if ok else 'FAIL'}")
    return ok


def check_forced_off(model):
    base = _serve(model, kv_cache_dtype="int8")  # default auto
    before = _kern_counters()
    # silence requires no retrace on a fresh jit either: clear caches so
    # the forced-dense serve traces its own program and STILL moves
    # nothing
    model.paged_programs.clear()
    off = _serve(model, paged_kernel="dense", kv_cache_dtype="int8")
    silent = _kern_counters() == before
    import jax
    same = off == base if jax.default_backend() == "cpu" else True
    ok = silent and same
    print(f"[kernel-gate] forced-off: byte-identical={same} "
          f"kernel-counter-silent={silent} {'PASS' if ok else 'FAIL'}")
    return ok


def main():
    model = _model()
    ok1 = check_equivalence(model)
    ok2 = check_counters(model)
    ok3 = check_warmup_zero_recompile(model)
    ok4 = check_forced_off(model)
    ok = ok1 and ok2 and ok3 and ok4
    try:
        import bench_ledger
        bench_ledger.append_entry("kernel_gate", {
            "kernel_equivalence_ok": 1.0 if ok1 else 0.0,
            "kernel_counters_ok": 1.0 if ok2 else 0.0,
            "kernel_warmup_ok": 1.0 if ok3 else 0.0,
            "kernel_forced_off_ok": 1.0 if ok4 else 0.0,
            "kernel_corpus": float(len(CORPUS))})
        print("[kernel-gate] ledger: appended kernel_gate")
    except Exception as e:  # noqa: BLE001 — ledger trouble is advisory
        print(f"[kernel-gate] ledger append skipped "
              f"({type(e).__name__}: {e})")
    print(f"[kernel-gate] {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
