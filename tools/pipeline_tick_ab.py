"""Pipeline-schedule A/B with hardware tick data.

The lockstep pipeline engine (distributed/pipeline.py) executes, per
device per tick, at most one of each phase:

  F  — chunk forward (run_chunk over the stage's Lc blocks)
  B  — combined backward: jax.vjp(chunk_fwd, x, params) — remats the
       forward and produces dx AND dw (1f1b / fthenb / packed styles)
  Bd — zb activation-grad half: jax.vjp(chunk_fwd, x) — remat + dx only
  W  — zb deferred weight-grad half: jax.vjp(chunk_fwd, params) —
       remat + dw only (pays the remat a second time)

A full P-stage mesh cannot run on one chip, but each phase is a
single-device computation — so we jit and time exactly those four
computations for a representative GPT stage ON THE REAL TPU and feed
the measured per-phase costs into the tick-table cost model
(pipeline_schedule.schedule_cost_report(costs=...)), whose tick/overlap
structure is exact (it replays the same tables the engine scans). The
output replaces the CPU-engine-only 1.67x zb-vs-1f1b number in
PARITY.md with hardware tick data.

Timing method: each phase is ONE jitted lax.scan of --iters serialized
phase executions ending in a scalar fetch, so host dispatch is paid once
and the quotient is device time (the method of
kernels/pallas/flash_attention.py _sweep_blocks). Every scan body
depends on the carry so XLA cannot hoist the loop-invariant computation.

Reference bar: pipeline_scheduler_pass/pipeline_zero_bubble.py (ZB-H1).

Usage:  python tools/pipeline_tick_ab.py [--out PIPELINE_TICKS.json]
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, ".")


def measure_phase_costs(hidden=1024, heads=16, seq=1024, mb=1, layers=3,
                        iters=10, dtype="bfloat16"):
    """Wall-clock per phase for one pipeline stage (Lc GPT blocks)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.pipeline import _functional_call
    from paddle_tpu.models.gpt import GPTBlock, GPTConfig

    cfg = GPTConfig(vocab_size=1024, hidden_size=hidden, num_heads=heads,
                    num_layers=layers, max_position_embeddings=seq)
    paddle.seed(0)
    blocks = [GPTBlock(cfg) for _ in range(layers)]
    on_tpu = jax.default_backend() not in ("cpu",)
    if on_tpu and dtype == "bfloat16":
        for b in blocks:
            b.to(dtype="bfloat16")
    params = [{k: p._data for k, p in b.named_parameters()}
              for b in blocks]

    def fwd(x, ps):
        for b, p in zip(blocks, ps):
            x = _functional_call(b, p, x)
        return x

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (mb, seq, hidden)), dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    cot = jnp.ones_like(x)

    def scan_run(body_fn):
        """body_fn(c, acc) -> (c2, acc2); returns a jitted scalar fn."""
        @jax.jit
        def run():
            def body(carry, _):
                return body_fn(*carry), ()
            (cf, accf), _ = lax.scan(body, (x, jnp.float32(0)), None,
                                     length=iters)
            return cf[0, 0, 0].astype(jnp.float32) + accf
        return run

    eps = x.dtype.type(1e-3)

    def f_body(c, acc):
        o = fwd(c, params)
        return o.astype(c.dtype), acc

    def b_body(c, acc):
        _, vjp = jax.vjp(fwd, c, params)
        dx, dps = vjp(cot)
        acc = acc + jax.tree.leaves(dps)[0].astype(jnp.float32).sum()
        return c + eps * dx.astype(c.dtype), acc

    def bd_body(c, acc):
        _, vjp = jax.vjp(lambda x_: fwd(x_, params), c)
        (dx,) = vjp(cot)
        return c + eps * dx.astype(c.dtype), acc

    def w_body(c, acc):
        # carry-dependence via c so XLA cannot hoist the invariant body
        _, vjp = jax.vjp(lambda ps_: fwd(c, ps_), params)
        (dps,) = vjp(cot)
        acc = acc + jax.tree.leaves(dps)[0].astype(jnp.float32).sum()
        return c + (eps * eps) * acc.astype(c.dtype), acc

    def timeit(run):
        float(run())  # compile + warm; scalar host fetch
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            float(run())
            best = min(best, time.perf_counter() - t0)
        return best / iters * 1e3  # ms per phase execution

    costs_ms = {
        "F": timeit(scan_run(f_body)),
        "B": timeit(scan_run(b_body)),
        "Bd": timeit(scan_run(bd_body)),
        "W": timeit(scan_run(w_body)),
    }
    meta = dict(hidden=hidden, heads=heads, seq=seq, mb=mb,
                layers_per_stage=layers, iters=iters,
                dtype=str(x.dtype),
                device=getattr(jax.devices()[0], "device_kind", "cpu"),
                backend=jax.default_backend())
    return costs_ms, meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="PIPELINE_TICKS.json")
    ap.add_argument("--P", type=int, default=8)
    ap.add_argument("--M", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    from paddle_tpu.distributed.pipeline_schedule import (
        schedule_cost_report)
    from paddle_tpu.utils import configure_compile_cache

    configure_compile_cache()
    costs_ms, meta = measure_phase_costs(
        hidden=args.hidden, seq=args.seq, layers=args.layers,
        iters=args.iters)
    rel = {k: v / costs_ms["F"] for k, v in costs_ms.items()}
    report = schedule_cost_report(args.P, args.M, costs=costs_ms)
    base = report.get("1f1b", {}).get("lockstep_cost") or 1.0
    for style, r in report.items():
        r["predicted_step_ms"] = round(r.pop("lockstep_cost"), 3)
        r["vs_1f1b"] = round(r["predicted_step_ms"] / base, 4)
        r["efficiency"] = round(r["efficiency"], 4)
    out = {
        "phase_costs_ms": {k: round(v, 4) for k, v in costs_ms.items()},
        "phase_costs_rel_F": {k: round(v, 3) for k, v in rel.items()},
        "config": dict(meta, P=args.P, M=args.M),
        "schedules": report,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
