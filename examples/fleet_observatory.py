"""Fleet observatory end-to-end: two serving replicas self-register in
a TCPStore, a FleetAggregator federates their telemetry, and a rolling
"deploy" drains one replica with zero dropped requests.

What it demos (docs/OBSERVABILITY.md "Fleet observatory",
docs/SERVING.md "Drain contract"):

  1. replica registry — ``serve_metrics(store=...)`` + TTL'd heartbeats;
  2. federation — ``/fleet/metrics`` sums counters / merges histogram
     buckets across replicas, ``/fleet/replicas`` health-scores them;
  3. drain — ``ServingEngine.drain()`` flips /readyz READY->CLOSED,
     finishes every in-flight request, and deregisters, exactly what a
     router needs for a rolling deploy.

Usage:
  JAX_PLATFORMS=cpu python examples/fleet_observatory.py
  JAX_PLATFORMS=cpu python examples/fleet_observatory.py --requests 8
"""

import argparse
import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6,
                    help="requests per replica")
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.models import Llama, LlamaConfig
    from paddle_tpu.profiler import fleet
    from paddle_tpu.serving import NotReadyError, ServingEngine

    paddle.seed(0)
    model = Llama(LlamaConfig.tiny())
    model.eval()
    rng = np.random.default_rng(0)

    # --- two replicas, one registry ------------------------------------
    store = TCPStore(is_master=True)
    replicas = []
    for i in (1, 2):
        eng = ServingEngine(model, max_batch=2, block_size=8,
                            max_seq_len=64, temperature=0.0,
                            bucket_cap=32, background=False)
        srv = eng.serve_metrics(store=store, replica_id=f"replica-{i}")
        print(f"[fleet] replica-{i} registered, scrape {srv.url()}")
        replicas.append(eng)
    for eng in replicas:
        for _ in range(args.requests):
            n = int(rng.integers(4, 20))
            eng.submit(rng.integers(0, 255, (n,)).astype("int64"),
                       max_new_tokens=args.max_new)
        eng.run_until_idle()

    # --- the aggregator: one plane over N processes --------------------
    agg = fleet.FleetAggregator(store=store)
    agg.refresh(force=True)
    with fleet.FleetServer(agg) as fs:
        body = json.loads(_get(fs.url("/fleet/replicas")))
        print(f"\n[fleet] {body['fleet']['replicas_live']} live "
              f"replica(s); fleet summary: "
              f"{ {k: v for k, v in body['fleet'].items()} }")
        for r in body["replicas"]:
            print(f"[fleet]   {r['replica_id']:<10} state={r['state']:<8}"
                  f" hb_age={r['heartbeat_age_s']:.2f}s "
                  f"health={r['health']:.3f} sha={r['git_sha']}")
        merged = [line for line in
                  _get(fs.url("/fleet/metrics")).splitlines()
                  if line.startswith("serving_completed")]
        print("\n[fleet] federated serving_completed series "
              "(per-replica + fleet sum):")
        for line in merged:
            print(f"[fleet]   {line}")

        # --- rolling deploy: drain replica-2 gracefully ----------------
        print("\n[deploy] draining replica-2 "
              "(in-flight finishes, new submits rejected) ...")
        eng2 = replicas[1]
        inflight = [eng2.submit(
            rng.integers(0, 255, (8,)).astype("int64"),
            max_new_tokens=args.max_new) for _ in range(2)]
        eng2.drain()
        done = sum(1 for h in inflight if h.status == "DONE")
        print(f"[deploy] drained: {done}/{len(inflight)} in-flight "
              f"finished DONE, lifecycle={eng2.lifecycle}")
        try:
            eng2.submit(rng.integers(0, 255, (8,)).astype("int64"))
        except NotReadyError as e:
            print(f"[deploy] new submit rejected: {e}")
        agg.refresh(force=True)
        body = json.loads(_get(fs.url("/fleet/replicas")))
        print(f"[deploy] registry now lists: "
              f"{[r['replica_id'] for r in body['replicas']]} "
              "(replica-2 deregistered)")
    for eng in replicas:
        eng.close()
    print("\n[fleet] done")


if __name__ == "__main__":
    main()
