"""Runtime flags registry.

Parity: reference `paddle/common/flags_native.cc:91` FlagRegistry + the
~172 `PHI_DEFINE_EXPORTED_*` flags (paddle/common/flags.cc), surfaced in
python as `paddle.set_flags/get_flags` and `FLAGS_*` env overrides.
"""

from __future__ import annotations

import os
import threading

_lock = threading.Lock()
_registry: dict[str, dict] = {}

# Settings epoch: bumped on every flag mutation (and by the AMP layer on
# autocast / op-stats toggles). Hot paths keep a snapshot of the handful
# of per-op gate values (core/dispatch._GATE) and re-read them ONLY when
# this counter moves — one int compare per op instead of a locked
# registry lookup per flag. Bumps are rare, so they take a dedicated
# lock (an unlocked `+= 1` could interleave and move the counter
# BACKWARD past a value a snapshot was taken at, masking a later
# change); reads stay lock-free — an int read can't tear, and a read
# racing a bump at worst triggers one extra refresh.
_EPOCH = 0
_epoch_lock = threading.Lock()


def _bump_epoch():
    global _EPOCH
    with _epoch_lock:
        _EPOCH += 1


def epoch():
    """Current settings epoch (see core/dispatch gate snapshot)."""
    return _EPOCH


def define_flag(name, default, help="", type=None):
    t = type or builtin_type(default)
    env = os.environ.get(name)
    value = _parse(env, t) if env is not None else default
    with _lock:
        _registry[name] = {"value": value, "default": default,
                           "help": help, "type": t}
        _bump_epoch()


def builtin_type(v):
    if isinstance(v, bool):
        return bool
    if isinstance(v, int):
        return int
    if isinstance(v, float):
        return float
    return str


def _parse(s, t):
    if t is bool:
        return s.lower() in ("1", "true", "yes", "on")
    return t(s)


def set_flags(flags: dict):
    """paddle.set_flags parity."""
    with _lock:
        try:
            for name, value in flags.items():
                if name not in _registry:
                    raise ValueError(f"unknown flag {name!r}")
                _registry[name]["value"] = _parse(
                    str(value), _registry[name]["type"]) \
                    if not isinstance(value, _registry[name]["type"]) \
                    else value
        finally:
            # bump even on an unknown-name error: names BEFORE the bad
            # one were already applied, and a skipped bump would leave
            # warm gate snapshots silently stale on those values
            _bump_epoch()


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    with _lock:
        return {name: _registry[name]["value"] for name in flags}


def flag(name):
    return _registry[name]["value"]


def get_exported_flag_info_map():
    with _lock:
        return {k: dict(v) for k, v in _registry.items()}


# -- the flag set (TPU-relevant subset of paddle/common/flags.cc) ---------
define_flag("FLAGS_check_nan_inf", False,
            "check every op output for NaN/Inf (reference flags.cc)")
define_flag("FLAGS_check_nan_inf_level", 0,
            "0: raise on nan/inf; 1: warn; 3: collect stats only")
define_flag("FLAGS_benchmark", False, "per-op timing")
define_flag("FLAGS_use_stride_kernel", True, "strided view kernels")
define_flag("FLAGS_eager_defer", True,
            "batch consecutive no-grad elementwise eager ops into one "
            "jitted dispatch (core/deferred.py) — one dispatch per "
            "chain instead of one per op")
define_flag("FLAGS_deferred_passes",
            os.environ.get("PADDLE_TPU_PASSES", "1").lower()
            not in ("0", "false", "off", "no"),
            "run the graph-optimization pass pipeline (paddle_tpu/passes:"
            " canonicalize, constant-fold, CSE, DCE) on deferred chains "
            "between capture and jit — smaller programs, canonical jit "
            "cache keys; PADDLE_TPU_PASSES=0 (or this flag) reverts to "
            "the verbatim capture-order compile")
define_flag("FLAGS_deferred_fusion",
            os.environ.get("PADDLE_TPU_FUSION", "1").lower()
            not in ("0", "false", "off", "no"),
            "extend the deferred-chain pass pipeline with the fusion "
            "tier (paddle_tpu/passes: batch identical distinct-leaf "
            "subtrees into one call, fuse single-consumer elementwise "
            "runs into super-nodes); keys the jit cache under the "
            "disjoint passes/v2 namespace so fused forms canonicalize; "
            "PADDLE_TPU_FUSION=0 (or this flag) keeps the cleanup-only "
            "passes/v1 pipeline")
def deferred_async_default(cpu_count=None):
    """Host-aware default for ``FLAGS_deferred_async``: off on a
    single-core host, on everywhere else. The async flush worker buys
    capture/execute OVERLAP, which needs a second core to run on — the
    PR 10 A/B measured ~0.9x on the 1-core CI proxy (pure thread
    handoff, nothing to overlap). An explicit setting always wins: the
    ``FLAGS_deferred_async`` env var overrides at import (define_flag
    reads it) and ``set_flags`` overrides at runtime; this function
    only picks the default when nobody said anything."""
    n = os.cpu_count() if cpu_count is None else cpu_count
    return (n or 2) > 1


define_flag("FLAGS_deferred_async", deferred_async_default(),
            "async deferred-chain flush (core/deferred.py): a chain "
            "hitting DEFER_CAP is submitted to the flush worker and its "
            "outputs become futures resolved lazily at host reads, so "
            "the host keeps capturing the next chain while the previous "
            "one compiles/executes; failures degrade to the synchronous "
            "ladder (async -> sync verbatim -> eager replay); 0 reverts "
            "to fully synchronous flushes byte-for-byte. Defaults OFF "
            "on single-core hosts (no parallelism to overlap — "
            "deferred_async_default); an explicit env/set_flags value "
            "wins", type=bool)
define_flag("FLAGS_deferred_inflight", 4,
            "bounded in-flight window for async deferred flushes: at "
            "most this many submitted-unfinished chains before "
            "submission blocks (backpressure, counted "
            "deferred.async.window_full); min 1")
define_flag("FLAGS_embedding_deterministic", 0,
            "deterministic embedding grad accumulation")
define_flag("FLAGS_cudnn_deterministic", False,
            "deterministic kernels (XLA is deterministic by default)")
define_flag("FLAGS_low_precision_op_list", 0, "collect AMP op stats")
define_flag("FLAGS_allocator_strategy", "auto_growth",
            "allocator strategy name (HBM is managed by PJRT)")
define_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92,
            "accepted for parity; PJRT preallocation is set via env")
define_flag("FLAGS_enable_api_kernel_fallback", True,
            "fall back to CPU when an op is unsupported on device")
define_flag("FLAGS_max_inplace_grad_add", 0, "grad accumulation chunking")
define_flag("FLAGS_enable_async_trace", False, "collective watchdog trace")
define_flag("FLAGS_distributed_timeout", 1800,
            "collective timeout seconds (coordination service barrier)")
define_flag("FLAGS_enable_collective_watchdog", False,
            "supervise each dispatched step with a timeout + flight "
            "records (reference comm_task_manager.h:37)")
define_flag("FLAGS_retry_max_attempts", 5,
            "core.resilience: retries per policy before the last "
            "exception propagates (per-call overridable)")
define_flag("FLAGS_retry_base_delay_ms", 50.0,
            "core.resilience: first backoff delay; doubles per retry")
define_flag("FLAGS_retry_max_delay_ms", 2000.0,
            "core.resilience: backoff cap per sleep")
define_flag("FLAGS_rendezvous_deadline", 120.0,
            "total seconds a rendezvous retry loop (TCPStore/rpc/elastic "
            "connect) may keep retrying before giving up")
define_flag("FLAGS_flush_degradation", True,
            "deferred-flush degradation ladder (core/deferred.py): "
            "pass-pipeline failure retries the verbatim compile, compile "
            "failure replays the chain op-by-op; off = strict mode, "
            "flush exceptions propagate")
define_flag("FLAGS_checkpoint_keep", 3,
            "retain-last-K sweep after each successful save_state_dict "
            "(versioned ckpt_* layout); 0 keeps every checkpoint")
define_flag("FLAGS_serving_max_queue", 256,
            "serving admission-queue bound (paddle_tpu/serving): submits "
            "beyond this raise QueueFullError — backpressure instead of "
            "unbounded host memory growth; 0 = unbounded")
define_flag("FLAGS_serving_prefill_budget", 512,
            "max prompt tokens prefilled per scheduler step (iteration-"
            "level scheduling: bounds prefill work per step so long "
            "prompts cannot starve running decodes); 0 = unlimited")
define_flag("FLAGS_trace_enable", True,
            "request-scoped tracing (profiler/tracing.py): record "
            "sampled spans (serving request lifecycle, deferred flush, "
            "rpc/store/checkpoint) into the in-process ring; off = every "
            "tracing entry point is a single global read")
define_flag("FLAGS_trace_sample", 1.0,
            "fraction of root traces sampled (decided once per trace at "
            "start_trace); children of an unsampled root cost the same "
            "as disabled tracing, so overhead scales with this rate")
define_flag("FLAGS_trace_ring", 4096,
            "span ring-buffer capacity (profiler/tracing.py): bounded "
            "memory — old spans age out; resize drops buffered history")
define_flag("FLAGS_serving_prefix_cache", True,
            "content-addressed prefix caching in the serving paged KV "
            "pool (inference/paged.py): block-aligned prompt chunks are "
            "rolling-hashed, shared read-only across requests with "
            "refcounts + copy-on-write, reclaimed LRU on demand; the "
            "scheduler admits cache-hitting requests at the cost of "
            "their UNCOVERED tokens only; 0 reverts to private-blocks "
            "behavior (read at Scheduler construction)")
define_flag("FLAGS_serving_prefill_bucket_cap", 1024,
            "serving prefill padded lengths round up to power-of-two "
            "buckets capped here (bounds the warm jit-cache footprint to "
            "log2(cap) prefill programs); 0 disables bucketing (pad to "
            "block multiple only)")
define_flag("FLAGS_serving_accounting", True,
            "per-request cost attribution + engine goodput accounting "
            "(profiler/accounting.py): each scheduler step's wall time "
            "is apportioned across the concurrent requests in proportion "
            "to tokens prefilled/decoded, compile time billed to the "
            "triggering request, re-prefill billed to the preemption; "
            "0 reverts to pre-accounting behavior byte-for-byte (read at "
            "Scheduler construction, like FLAGS_serving_prefix_cache)")
define_flag("FLAGS_slo_ttft_budget_us", 500000,
            "TTFT SLO budget in microseconds (profiler/alerts.py burn-"
            "rate rule slo.ttft_burn): observations above this bucket "
            "boundary burn the error budget")
define_flag("FLAGS_slo_itl_budget_us", 100000,
            "inter-token-latency SLO budget in microseconds (alerts "
            "rule slo.itl_burn)")
define_flag("FLAGS_slo_target", 0.99,
            "SLO target fraction (e.g. 0.99 = 99% of requests within "
            "budget); the burn rate is bad-fraction / (1 - target)")
define_flag("FLAGS_alert_burn_threshold", 1.0,
            "burn-rate level at which slo.*_burn alerts fire (1.0 = "
            "consuming the whole error budget at exactly the rate that "
            "exhausts it over the SLO window)")
define_flag("FLAGS_alert_interval_s", 10.0,
            "min seconds between automatic alert-rule evaluations "
            "(AlertManager.maybe_evaluate — the scheduler calls it per "
            "step; the /alerts endpoint also nudges it); each interval "
            "is one rolling delta window")
define_flag("FLAGS_alert_queue_depth", 8,
            "queue.growth alert floor: admission-queue depth must be at "
            "least this (and growing) before the rule fires")
define_flag("FLAGS_fleet", True,
            "fleet observatory (profiler/fleet.py): arms replica "
            "self-registration from ServingEngine.serve_metrics(store=) "
            "and the FleetAggregator's registry reads; 0 (or passing no "
            "store) is a byte-for-byte no-op — no heartbeat thread, no "
            "fleet.* counter movement")
define_flag("FLAGS_fleet_ttl_s", 15.0,
            "replica heartbeat TTL seconds: a replica re-registers its "
            "fleet-store entry every ttl/3; the aggregator treats a "
            "heartbeat older than the TTL as down (replica.down fires, "
            "the replica ages out of /fleet/replicas) and garbage-"
            "collects entries stale beyond 3x the TTL")
define_flag("FLAGS_fleet_scrape_timeout_s", 2.0,
            "per-replica HTTP scrape timeout for the FleetAggregator; "
            "a replica that cannot be scraped within it counts as a "
            "scrape failure (staleness feeds replica.down)")
define_flag("FLAGS_serving_aot_cache", True,
            "persistent AOT compile cache (serving/aot_cache.py): the "
            "serving-path jit entry points (llama paged prefill buckets "
            "/ extend / decode, deferred-chain programs) lower().compile"
            "() through an on-disk store of serialized XLA executables, "
            "so a fresh process with a warm cache boots zero-compile; "
            "armed only when FLAGS_aot_cache_dir names a directory; 0 "
            "reverts to plain jax.jit byte-for-byte with jit.aot.* "
            "counter silence")
define_flag("FLAGS_aot_cache_dir",
            os.environ.get("PADDLE_TPU_AOT_CACHE", ""),
            "directory of the persistent AOT compile cache (empty = "
            "disarmed); also settable via the PADDLE_TPU_AOT_CACHE env "
            "var. Entries are crc32-guarded and staged+os.replace-"
            "committed (checkpoint-v2 discipline); corrupt entries "
            "quarantine to *.corrupt-N and recompile")
define_flag("FLAGS_serving_router", True,
            "multi-replica router (serving/router.py): weights request "
            "placement by fleet health scores, refuses non-READY "
            "replicas, retries failed submits on the next-best replica "
            "and fails over requests whose replica died; 0 (read at "
            "Router construction, like FLAGS_serving_accounting) makes "
            "Router a byte-for-byte pass-through to its first replica "
            "with router.* counter silence")
define_flag("FLAGS_router_max_failovers", 3,
            "max times the router will re-submit one request after its "
            "replica died mid-flight before the engine error propagates "
            "(a completed request is NEVER re-submitted)")
define_flag("FLAGS_serving_admission", True,
            "deadline-aware admission + priority load shedding "
            "(serving/overload.py): an EWMA service-time model predicts "
            "queue-wait + TTFT at submit(), provably-unmeetable "
            "deadlines reject immediately with AdmissionRejected "
            "(carrying retry_after_s) instead of paying prefill then "
            "timing out, and under pressure watermarks the scheduler "
            "sheds lowest-priority/newest QUEUED requests to terminal "
            "status SHED; 0 reverts shedding + predictive rejection "
            "byte-for-byte with serving.shed / admission.predicted_"
            "ttft_us silence (read at Scheduler construction, the "
            "FLAGS_serving_accounting convention). NOTE: brownout-"
            "stage submit rejections ride FLAGS_serving_brownout and "
            "count serving.admission.rejected even with this flag off "
            "— all-flags-off is fully counter-silent (gate-pinned)")
define_flag("FLAGS_admission_optimism", 0.5,
            "admission-rejection conservatism: a deadline is treated as "
            "provably unmeetable only when predicted_ttft * optimism "
            "still exceeds it — at 0.5 even HALF the EWMA prediction "
            "must bust the deadline, so estimate error rejects late, "
            "never eagerly")
define_flag("FLAGS_shed_min_queue", 16,
            "load shedding / brownout floor: overload pressure is 0 "
            "while fewer requests than this are queued — a full KV pool "
            "with an empty queue is a busy engine keeping up, not "
            "overload (shedding only ever removes QUEUED requests)")
define_flag("FLAGS_shed_queue_frac", 0.75,
            "queue-depth pressure watermark as a fraction of "
            "FLAGS_serving_max_queue: depth past frac*max_queue reads "
            "as pressure >= 1.0 (shed territory)")
define_flag("FLAGS_shed_kv_frac", 0.95,
            "KV-occupancy pressure watermark: active/usable blocks past "
            "this fraction reads as pressure >= 1.0 (with a queued "
            "backlog; see FLAGS_shed_min_queue)")
define_flag("FLAGS_shed_wait_s", 30.0,
            "predicted-queue-wait pressure watermark in seconds: an "
            "EWMA-predicted drain time past this reads as pressure >= "
            "1.0")
define_flag("FLAGS_serving_brownout", True,
            "brownout ladder (serving/overload.py): an edge-triggered, "
            "hysteresis-guarded controller walks ordered degradation "
            "stages under SUSTAINED overload pressure — 1: clamp "
            "effective max_new_tokens, 2: reject low-priority submits, "
            "3: admit only the top priority class — exposed as the "
            "serving.brownout.stage gauge with flight-recorded "
            "transitions; 0 reverts byte-for-byte (read at Scheduler "
            "construction)")
define_flag("FLAGS_brownout_enter_steps", 3,
            "consecutive scheduler steps at pressure >= 1.0 before the "
            "brownout ladder escalates one stage (sustained-overload "
            "guard: a single spiky step never browns out)")
define_flag("FLAGS_brownout_exit_steps", 6,
            "consecutive steps at pressure <= FLAGS_brownout_exit_"
            "pressure before the ladder de-escalates one stage "
            "(hysteresis: recovery is deliberately slower than entry "
            "so the stage never flaps)")
define_flag("FLAGS_brownout_exit_pressure", 0.7,
            "pressure level that counts toward brownout exit; the band "
            "between this and 1.0 holds the current stage (neither "
            "counter advances)")
define_flag("FLAGS_brownout_clamp_tokens", 16,
            "brownout stage >= 1 clamps each submit's effective "
            "max_new_tokens to at most this (counted serving.brownout."
            "clamped); 0 disables the clamp stage")
define_flag("FLAGS_router_breaker", True,
            "per-replica circuit breakers in the multi-replica router "
            "(serving/router.py over core.resilience.CircuitBreaker): "
            "repeated submit failures open a replica's breaker and "
            "traffic skips it until a half-open probe succeeds; 0 "
            "reverts byte-for-byte with router.breaker.* counter "
            "silence (read at Router construction)")
define_flag("FLAGS_breaker_failures", 5,
            "core.resilience.CircuitBreaker default: consecutive "
            "recorded failures that open a closed breaker")
define_flag("FLAGS_breaker_reset_s", 30.0,
            "core.resilience.CircuitBreaker default: seconds an open "
            "breaker waits before allowing one half-open probe")
define_flag("FLAGS_kv_cache_dtype", "",
            "serving KV-cache block storage dtype (inference/paged.py): "
            "'int8' stores the paged K/V pools as int8 with per-(row, "
            "kv-head) absmax scales beside the pool (the quantization."
            "AbsmaxObserver formula), roughly DOUBLING the usable block "
            "pool for the same HBM — engines auto-size num_blocks by "
            "the honest byte ratio and occupancy()/pool_bytes() report "
            "it; '' (default) keeps full-precision pools byte-for-byte "
            "with serving.kv.quant.* silence (read at engine "
            "construction, the FLAGS_serving_prefix_cache convention)")
define_flag("FLAGS_serving_spec", False,
            "self-speculative decoding in the serving scheduler "
            "(serving/spec.py + Scheduler._decode_spec): a prompt-"
            "lookup n-gram proposer drafts up to FLAGS_serving_spec_"
            "tokens tokens per request (no second model) and ONE "
            "batched multi-position paged sweep verifies them, "
            "accepting the longest greedy-matching prefix and rolling "
            "back rejected rows' blocks before the next step; greedy "
            "outputs stay bit-identical to non-speculative decode "
            "(tools/spec_gate.py pins it) and the tier only engages at "
            "temperature 0; 0 (default) reverts byte-for-byte with "
            "serving.spec.* counter silence (read at Scheduler "
            "construction)")
define_flag("FLAGS_serving_spec_tokens", 4,
            "max draft tokens proposed per request per speculative "
            "step (the verify sweep is one static program of 1 + this "
            "many positions; min 1)")
define_flag("FLAGS_serving_spec_ngram", 3,
            "longest trailing n-gram the prompt-lookup proposer "
            "matches against the request's own context (falls back to "
            "shorter n-grams down to 1 before giving up)")
define_flag("FLAGS_serving_mesh", "",
            "serving device mesh as 'DATAxMODEL' (serving/mesh.py): "
            "e.g. '1x8' tensor-parallels the served Llama over 8 "
            "devices — attention heads, MLP hidden dims and the paged "
            "KV pool's kv-head axis shard along the model axis via "
            "NamedSharding (decode attention under an explicit "
            "jax.shard_map), while the data axis "
            "partitions scheduler slots/blocks into capacity slices. "
            "Axis sizes must divide jax.device_count() and the model "
            "axis must divide num_heads/num_kv_heads/intermediate_size "
            "(structured MeshAxisError otherwise). '' or '1x1' "
            "(default) is byte-for-byte single-device serving with "
            "serving.mesh.* counter silence (read at Scheduler "
            "construction, the FLAGS_serving_prefix_cache convention)")
define_flag("FLAGS_paged_kernel", "auto",
            "paged-attention decode kernel routing (inference/paged.py "
            "paged_decode_attention; docs/PERF.md 'Pallas serving-"
            "kernel tier'): 'auto' (default) routes to the fused Pallas "
            "kernel on TPU — including dequant-fused int8 pools and the "
            "chunked long-context variant — and to the dense XLA "
            "reference on CPU; 'pallas' forces the kernel everywhere "
            "(interpret mode on CPU — tier-1 testable); 'dense' forces "
            "the dense reference byte-for-byte with serving.kernel.* "
            "counter silence. Read ONCE at engine construction (the "
            "FLAGS_serving_prefix_cache convention); also gates the "
            "int8 weight-matmul kernel behind ConvertedInt8Linear "
            "(read at conversion)")
define_flag("FLAGS_serving_disagg", False,
            "disaggregated prefill/decode serving (serving/disagg.py): "
            "the two-stage pipeline routes each request to a prefill-"
            "role replica (bucket-ladder only, stops at first token), "
            "exports the prompt's finished KV blocks through the "
            "serving/kv_transfer.py crc-framed plane keyed by prefix "
            "digests, imports them into a decode-role replica's pool "
            "and admits the request straight into the batched decode "
            "step with ZERO re-prefill; greedy outputs stay bit-"
            "identical to co-located serving (fp32 and int8 pools — "
            "tools/disagg_gate.py pins it) and ANY transfer failure "
            "fails open to co-located serving on the prefill replica; "
            "0 (default) reverts byte-for-byte with serving.disagg.* "
            "counter silence (read at DisaggPipeline construction, the "
            "FLAGS_serving_prefix_cache convention)")
define_flag("FLAGS_fleet_skew_ratio", 2.5,
            "fleet.skew alert threshold: a replica whose TTFT p95 "
            "exceeds this multiple of the fleet median p95 (both from "
            "merged scrape buckets, with a min-sample floor) is flagged "
            "as the slow outlier a router should de-weight")
define_flag("FLAGS_fleet_cache", False,
            "fleet cache plane (serving/fleet_cache.py): each replica "
            "advertises a capped hot slice of its registered chunk "
            "digests through the fleet-registry heartbeat payload, the "
            "Router scales its health/(1+inflight) rank by predicted "
            "leading prefix coverage, and a chosen replica that covers "
            "LESS than the best advertising peer pulls the registered "
            "blocks over the serving/kv_transfer.py frame plane before "
            "admission instead of re-prefilling — with any scoring or "
            "pull failure failing open to plain health-ranked local "
            "prefill, bit-identical (digests only gate placement; "
            "tools/fleet_cache_gate.py pins it); 0 (default) reverts "
            "byte-for-byte with serving.fleet_cache.* counter silence "
            "(read at Router AND ServingEngine construction, the "
            "FLAGS_serving_prefix_cache convention)")
define_flag("FLAGS_fleet_cache_digests", 64,
            "fleet cache advertisement cap: how many hot registered "
            "full-chunk digests a replica's DigestPublisher folds into "
            "each heartbeat payload, hottest first (live-referenced "
            "blocks newest-registration-first, then the reclaimable "
            "LRU newest-first) — bounds heartbeat payload growth; a "
            "truncated advertisement only shortens the predictable "
            "leading coverage, never corrupts it")
define_flag("FLAGS_fleet_cache_weight", 2.0,
            "fleet cache coverage weight: the Router multiplies a "
            "candidate's health/(1+inflight) rank by (1 + weight * "
            "covered_fraction) — at the default a fully-covered idle "
            "replica outranks an uncovered idle one 3:1, and a loaded "
            "covered replica stops absorbing traffic once its inflight "
            "damping exceeds the boost (which is what spreads a "
            "shared-prefix storm onto peers, who then pull)")
define_flag("FLAGS_fleet_cache_publish_s", 1.0,
            "fleet cache in-process publication cadence, seconds: how "
            "often the router-side plane snapshots engine-bound "
            "replicas' advertisements on the submit path (store-less "
            "fleets — tests, gates, single-process demos); store-"
            "discovered replicas ride their registry heartbeat instead "
            "and ignore this")
define_flag("FLAGS_fleet_autoscale", False,
            "predictive fleet autoscaler (serving/autoscaler.py): a "
            "hysteresis controller (the serving/overload.py brownout "
            "school — edge-triggered, flight-recorded) over merged "
            "fleet pressure (per-replica overload pressure, queue "
            "fraction, brownout stage, and the fleet shed-rate delta) "
            "that spawns ONE warm replica through the caller's spawn "
            "callback after FLAGS_autoscale_enter_steps sustained "
            "over-pressure ticks and retires the least-loaded replica "
            "it spawned through the zero-drop drain contract after "
            "FLAGS_autoscale_exit_steps sustained calm ticks; 0 "
            "(default) makes update() a counter-silent no-op — "
            "serving.autoscale.* never moves, the fleet is never "
            "mutated (read at FleetAutoscaler construction, the "
            "FLAGS_serving_prefix_cache convention)")
define_flag("FLAGS_autoscale_enter_steps", 3,
            "autoscaler scale-up hysteresis: consecutive update() "
            "ticks at pressure >= 1.0 before ONE replica spawns (the "
            "BrownoutController enter_steps discipline; an in-band "
            "tick resets the count)")
define_flag("FLAGS_autoscale_exit_steps", 6,
            "autoscaler scale-down hysteresis: consecutive update() "
            "ticks at pressure <= FLAGS_autoscale_low before ONE "
            "spawned replica drains and retires — deliberately slower "
            "than scale-up (capacity is cheap, queue time is not)")
define_flag("FLAGS_autoscale_low", 0.3,
            "autoscaler calm watermark: fleet pressure at or below "
            "this reads as surplus capacity; between this and 1.0 is "
            "the hold band where both hysteresis accumulators reset")
define_flag("FLAGS_autoscale_max_replicas", 8,
            "autoscaler fleet-size ceiling: scale-up edges past this "
            "live engine-bound size are held (counted "
            "serving.autoscale.holds), never spawned")
