"""LLM serving end-to-end: continuous batching over a paged KV cache,
plus class-free deployment via the serialized StableHLO program.

The inference analogue of the reference's AnalysisPredictor +
block_multi_head_attention serving stack (SURVEY.md §3.6), TPU-native:
one jitted decode program with static shapes, block tables for paged KV,
slots admitted/released per request.

Usage:
  python examples/serve_llm.py                 # tiny model, synthetic
  JAX_PLATFORMS=cpu python examples/serve_llm.py --requests 6
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--shared", type=int, default=4,
                    help="concurrent requests sharing one system prompt "
                         "(prefix-cache demo)")
    ap.add_argument("--export", action="store_true",
                    help="also demo jit.save/load of the forward")
    ap.add_argument("--overload", action="store_true",
                    help="demo the overload control plane: flood the "
                         "engine past capacity with mixed priorities "
                         "and watch shedding, fast rejection, and the "
                         "brownout stage (docs/SERVING.md)")
    ap.add_argument("--spec", action="store_true",
                    help="demo self-speculative decoding "
                         "(FLAGS_serving_spec): the same corpus "
                         "decoded with and without prompt-lookup "
                         "drafts — bit-identical tokens, fewer steps; "
                         "prints acceptance rate and the tokens/step "
                         "delta from the registry (docs/SERVING.md "
                         "'Decode speed tiers')")
    args = ap.parse_args()

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.inference.paged import ContinuousBatchingEngine
    from paddle_tpu.models import Llama, LlamaConfig

    paddle.seed(0)
    model = Llama(LlamaConfig.tiny())
    model.eval()
    on_cpu = jax.default_backend() == "cpu"
    if not on_cpu:
        model.to(dtype="bfloat16")

    # --- continuous batching: requests arrive at different times --------
    eng = ContinuousBatchingEngine(
        model, max_batch=4, block_size=8, max_seq_len=128,
        temperature=0.0,
        dtype=__import__("jax.numpy", fromlist=["x"]).bfloat16
        if not on_cpu else __import__("jax.numpy",
                                      fromlist=["x"]).float32)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    rids = []
    for i in range(args.requests):
        prompt = rng.integers(3, model.config.vocab_size,
                              size=4 + 2 * i)
        rids.append(eng.add_request(prompt, max_new_tokens=args.max_new))
        # interleave arrival with decoding (continuous batching)
        if i % 2 == 1:
            eng.step()
    results = eng.run_to_completion()
    dt = time.perf_counter() - t0
    total_new = sum(len(results[r]) - 1 for r in rids if r in results) \
        if isinstance(results, dict) else args.requests * args.max_new
    print(f"served {args.requests} requests in {dt * 1000:.1f} ms "
          f"({total_new / dt:.1f} tok/s aggregate)")
    for rid in rids:
        out = results[rid] if isinstance(results, dict) else None
        if out is not None:
            print(f"  request {rid}: {len(out)} tokens -> "
                  f"{np.asarray(out).reshape(-1)[:8].tolist()}...")

    # --- the serving layer: streaming, deadlines, SLO telemetry --------
    # (docs/SERVING.md) — same engine underneath, plus admission
    # control, preemption instead of truncation, and token streaming
    from paddle_tpu.serving import ServingEngine

    with ServingEngine(model, max_batch=4, block_size=8, max_seq_len=128,
                       temperature=0.0, bucket_cap=64) as serving:
        prompt = rng.integers(3, model.config.vocab_size, size=7)
        handle = serving.submit(prompt, max_new_tokens=args.max_new,
                                deadline_s=120.0)
        streamed = list(handle.stream(timeout=300))
        print(f"serving: streamed {len(streamed)} tokens "
              f"(status={handle.status}) -> {streamed[:8]}...")
        # per-request bill (profiler/accounting.py): who paid for which
        # device step — queue/prefill/decode/compile split, attributed
        # device ms, prefix-covered tokens
        cost = handle.cost()
        if cost is not None:
            print(f"  cost: {cost.summary()}")
        print(f"  {serving.accounting.goodput_line()}")
    from paddle_tpu.profiler import metrics
    snap = metrics.snapshot("serving.")

    def _avg(name):  # histogram avg is None until it has observations
        v = snap[name]["avg"]
        return f"{v:.0f}us" if v is not None else "n/a"

    print(f"serving SLO: ttft_avg={_avg('serving.ttft_us')} "
          f"itl_avg={_avg('serving.itl_us')} "
          f"preempts={snap['serving.preempt']}")

    # --- prefix caching: N requests sharing a long system prompt ------
    # (FLAGS_serving_prefix_cache, docs/SERVING.md "Prefix caching"):
    # the first request prefills + registers the system prompt's
    # blocks; every later request maps them read-only and computes only
    # its own suffix — watch hit-rate climb and TTFT collapse
    with ServingEngine(model, max_batch=4, block_size=8, max_seq_len=128,
                       temperature=0.0, bucket_cap=64) as serving:
        system = rng.integers(3, model.config.vocab_size, size=48)
        suffix = lambda: rng.integers(  # noqa: E731
            3, model.config.vocab_size, size=4)
        # cold: full prefill, registers the shared prefix
        t0 = time.perf_counter()
        cold = serving.submit(np.concatenate([system, suffix()]),
                              max_new_tokens=args.max_new)
        cold.result(timeout=300)
        cold_ttft = time.perf_counter() - t0
        before = metrics.snapshot("serving.prefix.")
        t0 = time.perf_counter()
        shared = [serving.submit(np.concatenate([system, suffix()]),
                                 max_new_tokens=args.max_new)
                  for _ in range(args.shared)]
        firsts = [h.result(timeout=300)[0] for h in shared]
        warm_wall = time.perf_counter() - t0
        after = metrics.snapshot("serving.prefix.")
        hits = after["serving.prefix.hit_blocks"] - \
            before["serving.prefix.hit_blocks"]
        misses = after["serving.prefix.miss_blocks"] - \
            before["serving.prefix.miss_blocks"]
        computed = after["serving.prefix.computed_tokens"] - \
            before["serving.prefix.computed_tokens"]
        assert len(firsts) == args.shared
        print(f"prefix cache: {args.shared} shared-prompt requests "
              f"hit {hits}/{hits + misses} blocks "
              f"(rate {hits / max(hits + misses, 1):.2f}), computed "
              f"only {computed} prefill tokens; cold TTFT "
              f"{cold_ttft * 1000:.1f}ms vs {warm_wall * 1000:.1f}ms "
              f"for all {args.shared} warm requests together "
              f"(incl. one-off extend-program compile)")
        # the bills make the cache visible per request: the cold
        # request pays full prefill, warm ones are billed extend-only
        # (covered tokens free) — and the goodput line totals the run
        for name, h in [("cold", cold)] + \
                [(f"warm{i}", h) for i, h in enumerate(shared)]:
            c = h.cost()
            if c is not None:
                print(f"  cost[{name}]: {c.summary()}")
        print(f"  {serving.accounting.goodput_line()}")

    if args.overload:
        # --- overload control plane (serving/overload.py) ------------
        # flood a 2-slot engine ~8x past capacity: HIGH-priority
        # requests keep their deadlines while the LOW class sheds with
        # a retry-after hint, and a provably-unmeetable deadline is
        # rejected at submit instead of paying prefill then timing out
        from paddle_tpu.serving import AdmissionRejected, overload

        with ServingEngine(model, max_batch=2, block_size=8,
                           max_seq_len=128, temperature=0.0,
                           bucket_cap=64, max_queue=32,
                           background=False) as eng:
            for _ in range(3):  # prime the EWMA service-time model
                eng.submit(rng.integers(3, model.config.vocab_size,
                                        size=5), max_new_tokens=2)
                eng.run_until_idle()
            ov = eng.scheduler.overload
            ov.min_queue, ov.queue_frac = 3, 0.125  # demo watermarks
            handles = []
            for i in range(16):
                pri = overload.HIGH if i < 4 else (
                    overload.NORMAL if i < 8 else overload.LOW)
                prompt = rng.integers(3, model.config.vocab_size,
                                      size=6 + i % 4)
                handles.append((pri, eng.submit(
                    prompt, max_new_tokens=8, priority=pri,
                    deadline_s=300.0 if pri == overload.HIGH
                    else None)))
            eng.run_until_idle()
            by = {}
            for pri, h in handles:
                by.setdefault(pri, []).append(h)
            for pri, name in ((overload.HIGH, "HIGH"),
                              (overload.NORMAL, "NORMAL"),
                              (overload.LOW, "LOW")):
                hs = by.get(pri, [])
                statuses = [h.status for h in hs]
                line = f"overload: {name:<6} " + " ".join(statuses)
                sheds = [h for h in hs if h.status == "SHED"]
                if sheds and sheds[0].retry_after_s:
                    line += (f"  (retry after "
                             f"~{sheds[0].retry_after_s * 1e3:.0f}ms)")
                print(line)
            try:
                eng.submit(rng.integers(3, model.config.vocab_size,
                                        size=48),
                           max_new_tokens=8, deadline_s=1e-4)
            except AdmissionRejected as e:
                print(f"overload: unmeetable deadline rejected at "
                      f"submit — predicted TTFT "
                      f"{e.predicted_ttft_s * 1e3:.1f}ms, retry after "
                      f"~{e.retry_after_s * 1e3:.0f}ms (reason="
                      f"{e.reason})")
            snap = metrics.snapshot()
            print(f"overload: shed={snap['serving.shed']} "
                  f"admission.rejected="
                  f"{snap['serving.admission.rejected']} "
                  f"brownout.stage={snap['serving.brownout.stage']}")
            print(f"  {eng.accounting.goodput_line()}")

    if args.spec:
        # --- decode speed tiers: self-speculative decoding ------------
        # (FLAGS_serving_spec, docs/SERVING.md "Decode speed tiers"):
        # prompt-lookup drafts verified in one batched multi-position
        # sweep — greedy outputs bit-identical, fewer scheduler steps.
        # The corpus is the SAME repetitive family tools/spec_gate.py
        # pins (high acceptance for the seed-0 tiny model).
        from paddle_tpu.serving.spec import repetitive_prompts
        rep = repetitive_prompts()

        def run_tier(spec):
            outs, steps = [], 0
            with ServingEngine(model, max_batch=2, block_size=8,
                               max_seq_len=64, temperature=0.0,
                               bucket_cap=32, background=False,
                               spec=spec) as eng:
                s0 = metrics.snapshot("serving.")
                for p in rep:
                    h = eng.submit(p, max_new_tokens=24)
                    eng.run_until_idle()
                    outs.append(h.tokens())
                steps = metrics.snapshot("serving.")["serving.steps"] \
                    - s0["serving.steps"]
            return outs, steps

        b = metrics.snapshot("serving.spec.")
        base_outs, base_steps = run_tier(False)
        spec_outs, spec_steps = run_tier(True)
        a = metrics.snapshot("serving.spec.")
        proposed = a["serving.spec.proposed"] - \
            b["serving.spec.proposed"]
        accepted = a["serving.spec.accepted"] - \
            b["serving.spec.accepted"]
        assert spec_outs == base_outs, "speculative decode must be " \
            "bit-identical to plain greedy decode"
        print(f"spec decode: {base_steps} -> {spec_steps} steps for "
              f"the same {sum(len(o) for o in base_outs)} tokens "
              f"({base_steps / max(spec_steps, 1):.2f}x tokens/step), "
              f"drafts accepted {accepted}/{proposed} "
              f"(rate {accepted / max(proposed, 1):.2f}); outputs "
              f"bit-identical")

    # paged decode must agree with the dense-cache generate path
    prompt = rng.integers(3, model.config.vocab_size, size=6)
    dense = model.generate(paddle.to_tensor(prompt[None, :]),
                           max_new_tokens=8)
    eng2 = ContinuousBatchingEngine(
        model, max_batch=1, block_size=4, max_seq_len=64,
        dtype=__import__("jax.numpy", fromlist=["x"]).float32
        if on_cpu else __import__("jax.numpy", fromlist=["x"]).bfloat16)
    rid = eng2.add_request(prompt, max_new_tokens=8)
    paged = eng2.run_to_completion()[rid]
    d = np.asarray(dense.numpy()).reshape(-1)[len(prompt):]
    p = np.asarray(paged).reshape(-1)[:len(d)]
    assert (d == p).all(), (d, p)
    print("paged == dense greedy decode OK")

    if args.export:
        from paddle_tpu.static import InputSpec
        prefix = "/tmp/served_llm"
        # concrete batch: the decoder builds position ids/causal masks
        # with dim comparisons that symbolic batch can't resolve
        paddle.jit.save(model, prefix,
                        input_spec=[InputSpec([2, 16], "int64")])
        served = paddle.jit.load(prefix)
        ids = paddle.to_tensor(rng.integers(
            3, model.config.vocab_size, size=(2, 16)))
        ref = model(ids)
        out = served(ids)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), atol=1e-4)
        print(f"exported StableHLO program serves identically "
              f"({prefix}.pdmodel)")


if __name__ == "__main__":
    main()
