"""Mesh-sharded serving gate (ISSUE 15): the ``(data, model)`` serving
mesh through three pass/fail checks, in order of importance:

  1. equivalence — on an 8-host-device corpus
     (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) a
     ``FLAGS_serving_mesh=1x8`` (and a ``2x4``) serve of the tiny-TP
     Llama (``LlamaConfig.tiny_tp``) produces greedy outputs
     BIT-IDENTICAL to the 1x1 run on a mixed corpus, a shared-prefix
     corpus (equal prefix-cache hit/COW counters), and a small-pool
     corpus that forces preemption (equal preempt counts);
  2. warm-aot — at a FIXED mesh (1x8) a SECOND process against a warm
     AOT store boots zero-compile: ``warmup()`` loads serialized
     sharded executables (``jit.aot.misses == 0``) and the first
     served request triggers no XLA compile (the router_gate contract,
     at mesh — the mesh spec is folded into the cache fingerprint, so
     a 1x8 entry can never be served to a 1x1 engine);
  3. disarmed — ``FLAGS_serving_mesh`` unset is byte-for-byte
     identical to an explicit ``1x1`` with ``serving.mesh.*`` counter
     silence and NO slice-labeled gauges registered.

Every check runs in a subprocess because the forced host-device count
must be set before jax initializes. Exit 0 on pass, 1 on fail; one
line per check. Wired into tools/suite_gate.py beside the serving
gates, and appends a ``mesh_gate`` entry to the continuous-bench
ledger (tools/bench_ledger.py).
"""

import json
import os
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _child_env(n_devices, extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={n_devices}"])
    env.update(extra or {})
    return env


def _run_child(mode, n_devices, extra_env=None, args=(), timeout=900):
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__), mode, *args],
        cwd=REPO, env=_child_env(n_devices, extra_env),
        capture_output=True, text=True, timeout=timeout)
    row = None
    for line in reversed((p.stdout or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            row = json.loads(line)
            break
    if p.returncode != 0 or row is None:
        raise RuntimeError(
            f"mesh-gate child {mode} rc={p.returncode}: "
            f"{(p.stderr or '')[-500:]}")
    return row


# -- child bodies (run under the forced device count) ----------------------

def _model():
    import paddle_tpu as paddle
    from paddle_tpu.models import Llama, LlamaConfig

    paddle.seed(0)
    m = Llama(LlamaConfig.tiny_tp())
    m.eval()
    return m


def _serve(mesh, prompts, max_new=12, num_blocks=None, fresh_model=True):
    import jax.numpy as jnp

    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import ServingEngine

    model = _model()
    eng = ServingEngine(model, max_batch=4, block_size=8, max_seq_len=64,
                        temperature=0.0, bucket_cap=32, background=False,
                        dtype=jnp.float32, mesh=mesh,
                        num_blocks=num_blocks)
    s0 = metrics.snapshot("serving.")
    hs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_idle()
    s1 = metrics.snapshot("serving.")
    outs = [h.tokens() for h in hs]
    eng.close()

    def d(k):
        return (s1.get(k, 0) or 0) - (s0.get(k, 0) or 0)

    return outs, {k: d(k) for k in ("serving.preempt",
                                    "serving.prefix.hit_blocks",
                                    "serving.prefix.cow_copies")}


def child_equiv():
    import numpy as np

    rng = np.random.default_rng(7)
    mixed = [rng.integers(3, 250, size=s) for s in (9, 5, 14, 7, 21, 6)]
    sysp = rng.integers(3, 250, size=17)
    shared = [np.concatenate([sysp, rng.integers(3, 250, size=4)])
              for _ in range(4)]
    tight = [rng.integers(3, 250, size=9) for _ in range(4)]

    res = {}
    base_m, _ = _serve(None, mixed)
    m18, _ = _serve("1x8", mixed)
    m24, _ = _serve("2x4", mixed)
    res["mixed_1x8"] = base_m == m18
    res["mixed_2x4"] = base_m == m24
    base_s, cb = _serve(None, shared)
    s18, cs = _serve("1x8", shared)
    res["shared_equal"] = base_s == s18
    res["shared_hits"] = [cb["serving.prefix.hit_blocks"],
                          cs["serving.prefix.hit_blocks"]]
    res["shared_counters"] = cb == cs and \
        cb["serving.prefix.hit_blocks"] > 0
    base_t, pb = _serve(None, tight, max_new=24, num_blocks=13)
    t18, ps = _serve("1x8", tight, max_new=24, num_blocks=13)
    res["preempt_equal"] = base_t == t18
    res["preempts"] = [pb["serving.preempt"], ps["serving.preempt"]]
    res["preempt_nonzero"] = pb["serving.preempt"] > 0 and \
        pb["serving.preempt"] == ps["serving.preempt"]
    print(json.dumps(res))


def child_warm(cache_dir, phase):
    import numpy as np

    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import ServingEngine, aot_cache

    import jax.numpy as jnp

    aot_cache.configure(cache_dir)
    model = _model()
    eng = ServingEngine(model, max_batch=4, block_size=8, max_seq_len=64,
                        temperature=0.0, bucket_cap=32, background=False,
                        dtype=jnp.float32, mesh="1x8", ready=False)
    w0 = metrics.snapshot("jit.aot.")
    eng.warmup()
    w1 = metrics.snapshot("jit.aot.")
    c0 = metrics.snapshot("xla.")
    rng = np.random.default_rng(3)
    h = eng.submit(rng.integers(3, 250, size=9), max_new_tokens=8)
    eng.run_until_idle()
    c1 = metrics.snapshot("xla.")
    out = {"phase": phase,
           "misses": w1.get("jit.aot.misses", 0)
           - w0.get("jit.aot.misses", 0),
           "hits": w1.get("jit.aot.hits", 0) - w0.get("jit.aot.hits", 0),
           "stores": w1.get("jit.aot.stores", 0)
           - w0.get("jit.aot.stores", 0),
           "serve_compiles": c1.get("xla.compile.count", 0)
           - c0.get("xla.compile.count", 0),
           "tokens": len(h.tokens())}
    eng.close()
    print(json.dumps(out))


def child_disarmed():
    import numpy as np

    from paddle_tpu.profiler import metrics

    rng = np.random.default_rng(11)
    prompts = [rng.integers(3, 250, size=s) for s in (8, 13, 6)]
    m0 = metrics.snapshot("serving.mesh.")
    unset, _ = _serve(None, prompts)     # FLAGS_serving_mesh left ''
    one, _ = _serve("1x1", prompts)      # explicit trivial mesh
    m1 = metrics.snapshot("serving.mesh.")
    sliced = [k for k in metrics.snapshot("serving.kv.")
              if '{slice="' in k]
    print(json.dumps({"equal": unset == one, "mesh_silent": m0 == m1,
                      "no_slice_gauges": not sliced}))


# -- parent checks ---------------------------------------------------------

def check_equivalence():
    r = _run_child("--child-equiv", 8)
    ok = (r["mixed_1x8"] and r["mixed_2x4"] and r["shared_equal"]
          and r["shared_counters"] and r["preempt_equal"]
          and r["preempt_nonzero"])
    print(f"[mesh-gate] equivalence: 1x8={r['mixed_1x8']} "
          f"2x4={r['mixed_2x4']} shared={r['shared_equal']} "
          f"(hits {r['shared_hits']}) preempt={r['preempt_equal']} "
          f"(preempts {r['preempts']}) {'PASS' if ok else 'FAIL'}")
    return ok, r


def check_warm_aot():
    with tempfile.TemporaryDirectory() as td:
        cold = _run_child("--child-warm", 8, args=(td, "cold"))
        warm = _run_child("--child-warm", 8, args=(td, "warm"))
    ok = (cold["stores"] > 0 and cold["tokens"] == 8
          and warm["misses"] == 0 and warm["hits"] > 0
          and warm["serve_compiles"] == 0 and warm["tokens"] == 8)
    print(f"[mesh-gate] warm-aot@1x8: cold stored {cold['stores']} "
          f"sharded executables; warm process hits={warm['hits']} "
          f"misses={warm['misses']} first-serve compiles="
          f"{warm['serve_compiles']} {'PASS' if ok else 'FAIL'}")
    return ok, warm


def check_disarmed():
    r = _run_child("--child-disarmed", 8)
    ok = r["equal"] and r["mesh_silent"] and r["no_slice_gauges"]
    print(f"[mesh-gate] disarmed: unset==1x1={r['equal']} "
          f"mesh-silent={r['mesh_silent']} "
          f"no-slice-gauges={r['no_slice_gauges']} "
          f"{'PASS' if ok else 'FAIL'}")
    return ok


def main():
    ok1, eq = check_equivalence()
    ok2, warm = check_warm_aot()
    ok3 = check_disarmed()
    ok = ok1 and ok2 and ok3
    try:
        import bench_ledger
        bench_ledger.append_entry("mesh_gate", {
            "mesh_equivalence_ok": 1.0 if ok1 else 0.0,
            "mesh_warm_aot_hits": float(warm.get("hits", 0)),
            "mesh_warm_serve_compiles":
                float(warm.get("serve_compiles", 0)),
            "mesh_disarmed_ok": 1.0 if ok3 else 0.0})
        print("[mesh-gate] ledger: appended mesh_gate")
    except Exception as e:  # noqa: BLE001 — ledger trouble is advisory
        print(f"[mesh-gate] ledger append skipped "
              f"({type(e).__name__}: {e})")
    print(f"[mesh-gate] {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    if "--child-equiv" in sys.argv:
        child_equiv()
    elif "--child-warm" in sys.argv:
        i = sys.argv.index("--child-warm")
        child_warm(sys.argv[i + 1], sys.argv[i + 2])
    elif "--child-disarmed" in sys.argv:
        child_disarmed()
    else:
        sys.exit(main())
