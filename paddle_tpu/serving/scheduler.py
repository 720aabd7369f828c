"""Iteration-level continuous-batching scheduler (Orca-style) over the
paged KV cache.

Single-threaded policy core of the serving subsystem (thread safety is
the frontend's job — `serving.frontend.ServingEngine` holds one lock
around every entry point). Each ``step()`` is one scheduling iteration:

1. **sweep** — cancellations and expired deadlines (``core.resilience.
   Deadline``) finish at the step boundary and free their blocks;
2. **admit** — strict FCFS from a bounded queue, limited by free slots,
   free blocks, and a per-step *prefill token budget*
   (``FLAGS_serving_prefill_budget``) so a burst of long prompts cannot
   starve running decodes; admitted prompts prefill at a bucketed
   length (`serving.bucketing`) and stream their first token. With
   prefix caching on (``FLAGS_serving_prefix_cache``), a prompt's
   resident prefix blocks are mapped read-only instead of recomputed:
   the budget is charged for the *uncovered* tail only, and the
   prefill runs the tail-extend program (zero FLOPs for covered
   blocks);
3. **decode** — ONE jitted step for every live slot, dispatched one
   step ahead: a step's tokens stay on the device as the next step's
   input, and the host reads and emits them while that next step runs
   (``_decode``; docs/SERVING.md "The decode loop runs one step
   ahead"). Pool exhaustion
   preempts the newest-admitted victim (free blocks + requeue at the
   queue front for re-prefill) instead of truncating anyone —
   ``serving.preempt`` counts it, and greedy outputs stay bit-identical
   to an uncontended run because re-prefill replays prompt+generated
   and the prefill's sampled token is the next new token. With
   speculation armed (``FLAGS_serving_spec``, greedy only), the step
   instead runs ONE batched multi-position verify sweep over
   prompt-lookup drafts (``_decode_spec``; docs/SERVING.md "Decode
   speed tiers") — several tokens per request per step, still
   bit-identical, rejected rows rolled back. A model that declares
   ``tokens_per_block`` > 1 (block-diffusion decoding, ``models/sdar.py``)
   runs a block step instead (``_launch_block``; it too one step
   ahead): one forward of every slot's open block of L positions, which
   either unmasks some of them (on the device; nothing emitted) or
   commits the block (L tokens emitted at once, when the step is read).

Every request terminates in exactly one of ``DONE`` / ``CANCELLED`` /
``TIMEOUT`` / ``SHED`` (or ``ERROR`` if the engine itself died). SLO
telemetry goes to the always-on registry under ``serving.*`` (TTFT /
inter-token
latency histograms, queue/slot/KV-utilization gauges, admitted/decoded/
preempted counters) and is surfaced by ``profiler.summary()``.

With accounting armed (``FLAGS_serving_accounting``, default on), each
step's measured wall time is apportioned across the requests that did
work in it (``profiler/accounting.py``: tokens-proportional, compile
billed to the triggering request, re-prefill billed to the preemption)
into per-request ``CostReport``s and engine goodput, and the SLO
burn-rate alert rules (``profiler/alerts.py``) are evaluated at step
boundaries.

With the overload control plane armed (``FLAGS_serving_admission`` /
``FLAGS_serving_brownout``; ``serving/overload.py``), ``submit()``
additionally rejects provably-unmeetable deadlines immediately
(``AdmissionRejected`` with a ``retry_after_s``), each step sheds
lowest-priority/newest queued requests past the pressure watermarks
(terminal status ``SHED``, blocks never allocated), and a brownout
ladder degrades service gracefully under sustained overload.
"""

from __future__ import annotations

import functools
import math
import time

import numpy as np

from ..core import flags as flags_mod
from ..core import resilience
from ..inference.paged import (CapacityError, PagedKVCache,
                               kernel_route, quant_block_ratio,
                               resolve_kv_dtype, resolve_paged_kernel,
                               sized_num_blocks, validate_request)
from ..profiler import accounting as _accounting
from ..profiler import alerts as _alerts
from ..profiler import metrics as _metrics
from ..profiler import tracing as _tracing
from . import mesh as _mesh
from . import overload as _overload
from . import spec as _spec
from .bucketing import bucket_length
from .overload import AdmissionRejected

__all__ = ["RequestStatus", "ServingRequest", "Scheduler",
           "QueueFullError", "AdmissionRejected", "HandoffError"]


class HandoffError(RuntimeError):
    """A disaggregated handoff admission failed on the decode side:
    the imported prefix does not fully cover the prompt, or the
    replica is out of slots/blocks right now. Raised BEFORE the
    request exists — serving/disagg.py catches it and fails open to
    co-located serving (the request is never lost)."""


class QueueFullError(RuntimeError):
    """Admission queue at FLAGS_serving_max_queue: backpressure — the
    caller should retry later or shed load upstream. Carries structured
    fields (``queue_depth``, ``max_queue``, ``retry_after_s`` — the
    overload controller's predicted drain time, None when disarmed or
    unprimed) so routers and clients back off by data, not by parsing
    the message."""

    def __init__(self, message, *, queue_depth=None, max_queue=None,
                 retry_after_s=None):
        super().__init__(message)
        self.queue_depth = queue_depth
        self.max_queue = max_queue
        self.retry_after_s = retry_after_s


class RequestStatus:
    QUEUED = "QUEUED"
    RUNNING = "RUNNING"
    DONE = "DONE"
    CANCELLED = "CANCELLED"
    TIMEOUT = "TIMEOUT"
    SHED = "SHED"
    ERROR = "ERROR"

    TERMINAL = (DONE, CANCELLED, TIMEOUT, SHED, ERROR)


class ServingRequest:
    """One request's full lifecycle state. ``generated`` only ever
    appends (preemption keeps it), so handle readers see a stable
    prefix."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "deadline",
                 "on_token", "on_finish", "status", "generated", "slot",
                 "preempts", "admit_seq", "submitted_at", "admitted_at",
                 "first_token_at", "last_token_at", "cancel_requested",
                 "span", "cost", "priority", "est_tokens",
                 "retry_after_s", "prefill_only")

    def __init__(self, rid, prompt, max_new_tokens, deadline=None,
                 on_token=None, on_finish=None,
                 priority=_overload.NORMAL):
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.deadline = deadline
        self.on_token = on_token
        self.on_finish = on_finish
        self.status = RequestStatus.QUEUED
        self.generated = []
        self.slot = -1
        self.preempts = 0
        self.admit_seq = -1
        self.submitted_at = time.monotonic()
        self.admitted_at = None
        self.first_token_at = None
        self.last_token_at = None
        self.cancel_requested = False
        # root span of this request's trace: opened at submit, ended at
        # the terminal status; the null span when unsampled/disabled
        self.span = _tracing.NULL
        # CostReport bound by the accountant at submit; None disarmed
        self.cost = None
        # overload control plane (serving/overload.py): priority class
        # (smaller = more important), the controller's estimated
        # uncovered-prefill tokens, and — set only when this request is
        # load-SHED — the predicted back-off seconds for the caller
        self.priority = priority
        self.est_tokens = 0
        self.retry_after_s = None
        # disaggregated serving (serving/disagg.py): a prefill-stage
        # request finishes DONE at its first token — the decode stage
        # runs on another replica after the KV handoff
        self.prefill_only = False

    @property
    def trace_id(self):
        return self.span.trace_id

    @property
    def done(self):
        return self.status in RequestStatus.TERMINAL


# -- SLO instrumentation (always-on registry; see docs/SERVING.md) -------
# every bound the SLO histograms had (500 us .. 5 s on 1-2.5-5: the
# exposition and the alert rules name those ``le``) and between them a
# 1-2-5 series from 100 us to 10 s, so that a percentile read off
# /metrics lands within one bucket of what a client measures
_US_BOUNDS = tuple(sorted(
    {500, 1000, 2500, 5000, 10000, 25000, 50000, 100000, 250000, 500000,
     1000000, 5000000}
    | {m * 10 ** e for e in range(2, 7) for m in (1, 2, 5)} | {10 ** 7}))
_m_admitted = _metrics.counter("serving.admitted")
_m_decoded = _metrics.counter("serving.decoded_tokens")
_m_preempt = _metrics.counter("serving.preempt")
_m_done = _metrics.counter("serving.completed")
_m_cancelled = _metrics.counter("serving.cancelled")
_m_timeout = _metrics.counter("serving.timeout")
_m_rejected = _metrics.counter("serving.rejected")
_m_shed = _metrics.counter("serving.shed")
_m_errors = _metrics.counter("serving.errors")
_m_cb_errors = _metrics.counter("serving.callback_errors")
_m_steps = _metrics.counter("serving.steps")
# decode dispatches made while the step before was still unread on the
# device, and those made after reading it (the first step after idle or
# a drain, the speculative path)
_m_ahead = _metrics.counter("serving.decode.ahead")
_m_in_order = _metrics.counter("serving.decode.in_order")
# context tokens (of K and of V) every layer's decode attention read:
# sum of seq_len + 1 over the live slots of each decode step
_m_ctx_tokens = _metrics.counter("serving.decode.context_tokens")
# slots whose recurrent state a decode step updated, summed over the
# steps (a model with state-space layers; silent otherwise)
_m_state_slot_steps = _metrics.counter("serving.ssm.state_slot_steps")
_h_ttft = _metrics.histogram("serving.ttft_us", bounds=_US_BOUNDS)
_h_itl = _metrics.histogram("serving.itl_us", bounds=_US_BOUNDS)
_h_queue_wait = _metrics.histogram("serving.queue_wait_us",
                                   bounds=_US_BOUNDS)
_h_step = _metrics.histogram("serving.step_us", bounds=_US_BOUNDS)
_g_queue = _metrics.gauge("serving.queue.depth")
_g_running = _metrics.gauge("serving.slots.running")
_g_blocks = _metrics.gauge("serving.kv.blocks_used")
_g_util = _metrics.gauge("serving.kv.utilization")
# prefix-cache economics: tokens the prefill actually computed (padded;
# covered tokens cost zero FLOPs — tools/prefix_gate.py pins this),
# blocks currently backing >1 slot, and reclaimable cached blocks
_m_prefix_computed = _metrics.counter("serving.prefix.computed_tokens")
_g_shared = _metrics.gauge("serving.kv.shared_blocks")
_g_cached = _metrics.gauge("serving.kv.cached_blocks")
# decode speed tiers (docs/SERVING.md "Decode speed tiers"): draft
# tokens proposed/accepted/rejected by the speculative verify sweep,
# its per-step acceptance rate, and the quantized-pool facts (bits +
# honest effective-capacity multiplier). All silent when both flags
# are off — tools/spec_gate.py pins the silence.
_m_spec_proposed = _metrics.counter("serving.spec.proposed")
_m_spec_accepted = _metrics.counter("serving.spec.accepted")
_m_spec_rejected = _metrics.counter("serving.spec.rejected")
_m_spec_steps = _metrics.counter("serving.spec.steps")
_h_spec_accept = _metrics.histogram(
    "serving.spec.accept_rate",
    bounds=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
_g_kv_quant_bits = _metrics.gauge("serving.kv.quant.bits")
_g_kv_quant_mult = _metrics.gauge(
    "serving.kv.quant.capacity_multiplier")
# block-diffusion decoding (docs/SERVING.md "Block-diffusion decoding"):
# slot-forwards that denoised and that committed, blocks committed,
# positions unmasked; and what the expert layers of those forwards
# routed (summed over layers from the counts the block step returns with
# its tokens): rows, experts that got any, the fullest expert's rows
_m_blk_denoise = _metrics.counter("serving.blockdiff.denoise_forwards")
_m_blk_commit = _metrics.counter("serving.blockdiff.commit_forwards")
_m_blk_blocks = _metrics.counter("serving.blockdiff.blocks_committed")
_m_blk_unmasked = _metrics.counter("serving.blockdiff.tokens_unmasked")
_m_moe_rows = _metrics.counter("serving.moe.rows")
_m_moe_hit = _metrics.counter("serving.moe.experts_hit")
_m_moe_max = _metrics.counter("serving.moe.max_rows")
# per-THREAD cumulative backend-compile seconds (profiler.metrics'
# jax.monitoring listener): deltas around a prefill/decode dispatch
# attribute compile cost to the request that triggered it — a
# concurrent engine's compile on another thread never leaks into this
# scheduler's bills (profiler/accounting.py)
_compile_s = _metrics.thread_compile_seconds
_phase = _tracing.phase
# same delta discipline for compile seconds the AOT cache SAVED
# (serving/aot_cache.py): a dispatch that loaded a serialized
# executable bills the avoided compile as aot_saved_us — informational
# (never part of the closure sum), but per-request like compile itself
_aot_saved_s = None


def _saved_s():
    global _aot_saved_s
    if _aot_saved_s is None:
        from .aot_cache import thread_saved_seconds
        _aot_saved_s = thread_saved_seconds
    return _aot_saved_s()


@functools.cache
def _token_merge():
    import jax
    import jax.numpy as jnp

    def serving_token_merge(prev, host, fresh):
        return jnp.where(fresh, host, prev)
    return jax.jit(serving_token_merge)


def merge_tokens(prev, host, fresh):
    """A decode step's token input made on the device: ``prev``, what
    the step before returned for the next (a device array, never read
    here: a token a slot [max_batch], or a block-diffusion model's open
    blocks [max_batch, .]), with ``host`` put in at the slots ``fresh``
    marks — the slots admitted since, whose first token (or open block)
    the host has."""
    fresh = np.asarray(fresh, bool)
    host = np.asarray(host, np.int32)
    behind = prev.shape[0] - host.shape[0]
    if behind > 0 and prev.ndim == 1:
        # what a model packs behind its tokens (``decode_extras``) is
        # the step's own: only the tokens are merged
        host, fresh = np.pad(host, (0, behind)), np.pad(fresh, (0, behind))
    return _token_merge()(prev, host,
                          fresh.reshape(fresh.shape + (1,) * (prev.ndim - 1)))


def _count_expert_rows(expert_rows):
    """``serving.moe.*`` from a step's [layers, experts] rows."""
    _m_moe_rows.inc(int(expert_rows.sum()))
    _m_moe_hit.inc(int((expert_rows > 0).sum()))
    _m_moe_max.inc(int(expert_rows.max(axis=1).sum()))


class _Flight:
    """A batched decode program dispatched and not read yet: what it
    returned (still device arrays), the request each slot ran for, and
    what timing the read-back needs."""

    __slots__ = ("toks", "feed", "reqs", "owed", "block", "step_no",
                 "t_ns", "comp_us", "built", "timed")

    def __init__(self, toks, step_no, t_ns, comp_us, built):
        # what the host reads back, and what the next step is fed on the
        # device: the same array of tokens on the plain path; a block
        # step's packed results and the open blocks after it
        self.toks = self.feed = toks
        self.reqs = {}  # slot -> request it ran for
        # [max_batch]: tokens the read-back will hand each slot by count
        # (1 a live slot; a block step: what its commits hold)
        self.owed = None
        # a block step's own: ``_launch_block`` says what
        self.block = None
        self.step_no = step_no
        self.t_ns = t_ns  # as the dispatch began
        self.comp_us = comp_us
        # the dispatch compiled or loaded its program: read in order, so
        # that the step that paid for it bills it to its own requests
        self.built = built
        # False once a prefill was read back behind it: when its tokens
        # arrived can no longer be seen
        self.timed = True


class Scheduler:
    """See module docstring. NOT thread-safe — callers serialize."""

    def __init__(self, model, *, max_batch=8, block_size=16,
                 max_seq_len=2048, num_blocks=None, temperature=0.0,
                 eos_token_id=None, dtype=None,
                 prefill_token_budget=None, max_queue=None,
                 bucket_cap=None, prefix_cache=None, accounting=None,
                 admission=None, brownout=None, kv_cache_dtype=None,
                 spec=None, spec_tokens=None, mesh=None,
                 paged_kernel=None):
        import jax.numpy as jnp

        cfg = model.config
        self.model = model
        # positions a decode step holds a slot: 1, or a block-diffusion
        # model's block length. What the model declares picks the decode
        # path; nothing else does.
        self._block_len = int(getattr(model, "tokens_per_block", 1))
        if self._block_len > 1 and (block_size % self._block_len
                                    or max_seq_len % self._block_len):
            raise ValueError(
                f"serving: block_size {block_size} and max_seq_len "
                f"{max_seq_len} must be multiples of the model's block "
                f"length {self._block_len}: a KV page may not end "
                "inside a block.")
        self.temperature = temperature
        self.eos_token_id = eos_token_id
        self.max_seq_len = max_seq_len
        mbps = math.ceil(max_seq_len / block_size)
        # mesh-sharded serving (FLAGS_serving_mesh, read ONCE at
        # construction like prefix_cache): the model axis tensor-
        # parallels params + KV pools via NamedSharding, the data axis
        # partitions slots/blocks into capacity slices; None (the
        # default '' / '1x1') is byte-for-byte single-device serving
        # with serving.mesh.* silence (serving/mesh.py)
        self.mesh = _mesh.resolve_serving_mesh(mesh)
        if self.mesh is not None:
            self.model.apply_serving_mesh(self.mesh)
            _mesh.note_engine(self.mesh)
        # int8 KV block storage (FLAGS_kv_cache_dtype, read ONCE at
        # construction like prefix_cache): default pool sizing grows by
        # the honest byte ratio — the same HBM budget holds ~2x the
        # blocks, compounding the prefix cache's capacity multiplier
        kv_dtype = resolve_kv_dtype(
            flags_mod.flag("FLAGS_kv_cache_dtype")
            if kv_cache_dtype is None else kv_cache_dtype)
        if self._block_len > 1 and kv_dtype == "int8":
            raise ValueError(
                "serving: int8 KV (FLAGS_kv_cache_dtype) is not served "
                "with a block-diffusion model: its block step has no "
                "quantized program.")
        # paged-attention kernel routing (FLAGS_paged_kernel, read ONCE
        # at construction like kv_cache_dtype): the resolved mode rides
        # into every decode dispatch so the traced programs bake the
        # route; `kernel_route` names where it lands (pallas / interpret
        # / dense) for spans and gates
        self.kernel_mode = resolve_paged_kernel(paged_kernel)
        self.kernel_route = kernel_route(self.kernel_mode)
        hd = cfg.head_dim
        compute_dt = dtype if dtype is not None else jnp.bfloat16
        num_blocks = sized_num_blocks(
            num_blocks, max_batch, mbps, kv_dtype, hd, compute_dt)
        # layers that carry state from step to step instead of K and V
        # (models/jamba.py): the cache holds it beside the pools, and
        # what cannot be served with it is refused here, by what the
        # model declares
        state_spec = getattr(model, "recurrent_state", None)
        # one row a token a layer shared by all heads (latent attention,
        # models/xing.py): another pool geometry in the same cache
        latent_rows = getattr(model, "latent_rows", None)
        # what a prefill call takes beside its prompt: the kernel route,
        # where the model's prefill has a kernel to route (a scan over
        # the state, block attention), and nothing where it has none
        self.prefill_route = {"kernel_mode": self.kernel_mode} \
            if state_spec is not None or latent_rows is not None \
            or self._block_len > 1 else {}
        self.cache = PagedKVCache(
            getattr(model, "kv_cache_layers", cfg.num_layers),
            cfg.num_kv_heads, hd, recurrent_state=state_spec,
            latent_rows=latent_rows, num_blocks=num_blocks,
            block_size=block_size, max_blocks_per_seq=mbps,
            max_batch=max_batch, dtype=compute_dt, kv_dtype=kv_dtype,
            pool_sharding=(self.mesh.kv_pool_sharding()
                           if self.mesh is not None else None),
            scale_sharding=(self.mesh.kv_scale_sharding()
                            if self.mesh is not None else None),
            num_slices=(self.mesh.data if self.mesh is not None else 1))
        # per-slice KV gauges (slice-id label; docs/OBSERVABILITY.md):
        # registered only when the mesh is armed, so the disarmed
        # exposition is byte-for-byte pre-mesh
        self._slice_gauges = [
            {k: _metrics.gauge(f"serving.kv.{k}", labels={"slice": str(i)})
             for k in ("active_blocks", "free_blocks", "shared_blocks",
                       "cached_blocks")}
            for i in range(self.cache.num_slices)] \
            if self.mesh is not None else []
        if self.cache.quantized:
            _g_kv_quant_bits.set(8)
            _g_kv_quant_mult.set(round(
                quant_block_ratio(hd, compute_dt), 4))
        # self-speculative decoding (FLAGS_serving_spec, read ONCE at
        # construction): greedy-only — sampled decode has no cheap
        # accept rule that keeps outputs distribution-exact, so any
        # temperature > 0 disables the tier (documented flag matrix)
        armed_spec = (bool(flags_mod.flag("FLAGS_serving_spec"))
                      if spec is None else bool(spec))
        self.spec_tokens = max(int(
            flags_mod.flag("FLAGS_serving_spec_tokens")
            if spec_tokens is None else spec_tokens), 1)
        self.spec_ngram = max(
            int(flags_mod.flag("FLAGS_serving_spec_ngram")), 1)
        if self._block_len > 1 and armed_spec:
            raise ValueError(
                "serving: speculation (FLAGS_serving_spec) is not served "
                "with a block-diffusion model: a step already yields a "
                "block of tokens.")
        if self._block_len > 1 and temperature != 0.0:
            raise ValueError(
                "serving: a block-diffusion model is served greedy "
                "(temperature 0): the unmasking rule ranks arg-max "
                "confidences.")
        if state_spec is not None and armed_spec:
            raise ValueError(
                "serving: speculation (FLAGS_serving_spec) is not served "
                "with a model that carries recurrent state: rejected "
                "drafts roll K and V back by truncating blocks, and a "
                "recurrence has no such rewind.")
        if latent_rows is not None and armed_spec:
            raise ValueError(
                "serving: speculation (FLAGS_serving_spec) is not served "
                "with a latent cache: the verify sweep attends several "
                "positions a slot over K and V a head, and no such "
                "program reads latent rows yet.")
        self.spec = armed_spec and temperature == 0.0
        self.prefill_token_budget = (
            flags_mod.flag("FLAGS_serving_prefill_budget")
            if prefill_token_budget is None else int(prefill_token_budget))
        self.max_queue = (flags_mod.flag("FLAGS_serving_max_queue")
                          if max_queue is None else int(max_queue))
        self.bucket_cap = (
            flags_mod.flag("FLAGS_serving_prefill_bucket_cap")
            if bucket_cap is None else int(bucket_cap))
        # prefix caching: read ONCE at construction (mid-flight flag
        # flips would mix shared and private accounting); off = the
        # cache never registers a chunk and behaves exactly as before
        self.prefix_cache = (
            bool(flags_mod.flag("FLAGS_serving_prefix_cache"))
            if prefix_cache is None else bool(prefix_cache))
        # cost attribution (profiler/accounting.py): read ONCE at
        # construction like prefix_cache; disarmed = the preallocated
        # null accountant, every hook a no-op — behavior byte-for-byte
        # pre-accounting (tools/accounting_gate.py pins both)
        armed = (bool(flags_mod.flag("FLAGS_serving_accounting"))
                 if accounting is None else bool(accounting))
        self.accounting = _accounting.Accountant(config=cfg) if armed \
            else _accounting.NULL
        # SLO burn-rate alert rules ride with accounting: evaluated at
        # step boundaries (rate-limited by FLAGS_alert_interval_s) and
        # served from the /alerts endpoint when serve_metrics attaches
        self.alerts = _alerts.AlertManager() if armed else None
        # overload control plane (serving/overload.py): deadline-aware
        # admission + priority shedding (FLAGS_serving_admission) and
        # the brownout ladder (FLAGS_serving_brownout), read ONCE at
        # construction like prefix_cache/accounting; both off = the
        # preallocated null controller, behavior byte-for-byte
        # pre-overload (tools/overload_gate.py pins the revert)
        adm = (bool(flags_mod.flag("FLAGS_serving_admission"))
               if admission is None else bool(admission))
        brw = (bool(flags_mod.flag("FLAGS_serving_brownout"))
               if brownout is None else bool(brownout))
        self.overload = _overload.OverloadController(
            admission=adm, brownout=brw) if (adm or brw) \
            else _overload.NULL
        self.queue: list[ServingRequest] = []
        self.running: dict[int, ServingRequest] = {}  # slot -> request
        self.finished: dict[int, ServingRequest] = {}  # rid -> request
        self._next_rid = 0
        self._next_admit_seq = 0
        self._last_tok = np.zeros((max_batch,), np.int64)
        self._remaining = np.zeros((max_batch,), np.int64)
        self._step_no = 0  # ``serving.steps`` as the running step began
        # the plain and the block path run one step ahead: the step
        # dispatched and not read yet, when the host last saw a step's
        # tokens arrive (perf_counter_ns), and the last step time it
        # could see (us)
        self._flight = None
        self._seen_ns = 0
        self._dec_us = None
        # block-diffusion decoding, per slot. The open blocks live on the
        # device (the block step takes and returns them); the host has
        # the counts every launch is made from — how many leading
        # positions the prompt gave the open block and how many denoising
        # forwards have been launched on it — and the block's ids and
        # which of its positions are still masked (state, never read off
        # the ids: a prompt may hold the mask id) as of the last step
        # read: what a slot admitted since opens with, and what a launch
        # with nothing in flight is fed
        n_blk = self._block_len if self._block_len > 1 else 0
        self._blk_ids = np.zeros((max_batch, n_blk), np.int64)
        self._blk_masked = np.zeros((max_batch, n_blk), bool)
        self._blk_given = np.zeros((max_batch,), np.int64)
        self._blk_denoised = np.zeros((max_batch,), np.int64)
        # called with a dict for every slot-forward of a block step when
        # set (the benchmark's reference check records through it)
        self.block_observer = None
        # (slot, list) when set, for a model with recurrent state: every
        # plain decode step appends what the slot's recurrence was fed
        # (``Jamba.paged_decode_step`` reads it under the cache's lock;
        # the benchmark's check replays the recurrence from it). A model
        # that declares ``decode_tap`` takes it too
        self.state_observer = None
        self._observed = state_spec is not None \
            or bool(getattr(model, "decode_tap", False))
        # [layers, experts] rows routed in a plain decode step, from the
        # array read back, where the model packs them behind its tokens
        self._expert_rows = getattr(model, "decode_expert_rows", None)

    # -- submission / cancellation ------------------------------------

    def submit(self, prompt_ids, max_new_tokens=32, *, deadline=None,
               priority=None, on_token=None, on_finish=None,
               prefill_only=False):
        """Validate + enqueue; returns the ServingRequest. Raises
        ValueError on malformed or never-servable input (never corrupts
        the cache, never hangs admission), QueueFullError past the
        admission bound, and — overload control armed —
        AdmissionRejected for a provably-unmeetable deadline or a
        priority the brownout ladder's current stage refuses (both
        BEFORE any queueing: fail fast, never pay prefill for a
        request that cannot finish). ``priority`` is an int class,
        smaller = more important (default overload.NORMAL).

        ``prefill_only`` is the disaggregation prefill stage (serving/
        disagg.py): the request runs ONLY the bucket-ladder prefill and
        finishes ``DONE`` at its first token, leaving the prompt's KV
        blocks registered in the prefix index — exactly the state
        ``serving/kv_transfer.export_prefix`` serializes. It requires
        the prefix cache (without ``commit_prefix`` the blocks would
        free on finish and there would be nothing to hand off)."""
        prompt = validate_request(prompt_ids, max_new_tokens,
                                  self.max_seq_len, self.cache,
                                  who="serving.submit")
        if self._block_len > 1:
            if prefill_only:
                raise ValueError(
                    "serving.submit: a block-diffusion model is not "
                    "served disaggregated: its prefill samples no first "
                    "token to hand off.")
            # the last block is written whole, past max_new_tokens
            whole = -(-(prompt.size + int(max_new_tokens))
                      // self._block_len) * self._block_len
            validate_request(prompt, whole - prompt.size,
                             self.max_seq_len, self.cache,
                             who="serving.submit")
        if prefill_only and self.cache.state_spec is not None:
            raise ValueError(
                "serving.submit: prefill_only hands a prompt's K and V "
                "blocks to another replica; this model also carries "
                "recurrent state, which no block holds and no transfer "
                "frame carries.")
        if prefill_only and self.cache.latent_spec is not None:
            raise ValueError(
                "serving.submit: prefill_only hands a prompt's blocks to "
                "another replica in a transfer frame, and no frame "
                "carries latent rows yet.")
        if prefill_only and not self.prefix_cache:
            raise ValueError(
                "serving.submit: prefill_only requires the prefix "
                "cache (FLAGS_serving_prefix_cache) — finished blocks "
                "must stay registered for export")
        pri = _overload.NORMAL if priority is None else int(priority)
        if self.max_queue and len(self.queue) >= self.max_queue:
            _m_rejected.inc()
            raise QueueFullError(
                f"serving.submit: admission queue full "
                f"({len(self.queue)} >= {self.max_queue})",
                queue_depth=len(self.queue), max_queue=self.max_queue,
                retry_after_s=self.overload.queue_retry_after(self))
        # the overload gate: brownout priority floor + predictive
        # deadline rejection; also clamps max_new_tokens at stage >= 1
        # and estimates this prompt's uncovered-prefill tokens (the
        # quantity the pressure/wait predictions sum over)
        est, max_new_tokens = self.overload.admit(
            self, prompt, int(max_new_tokens), deadline, pri)
        req = ServingRequest(self._next_rid, prompt, max_new_tokens,
                             deadline=deadline, on_token=on_token,
                             on_finish=on_finish, priority=pri)
        req.est_tokens = est
        req.prefill_only = bool(prefill_only)
        self._next_rid += 1
        req.span = _tracing.start_trace(
            "serving.request", rid=req.rid, prompt_len=len(prompt),
            max_new_tokens=int(max_new_tokens))
        self.accounting.attach(req)
        self.queue.append(req)
        _g_queue.set(len(self.queue))
        return req

    def admit_handoff(self, prompt_ids, first_token, max_new_tokens=32,
                      *, deadline=None, priority=None, on_token=None,
                      on_finish=None, trace_parent=None,
                      transfer_us=0.0, transfer_bytes=0,
                      handoff_id=None):
        """Disaggregated decode-stage admission (serving/disagg.py):
        the prompt's KV blocks were just imported (``serving/
        kv_transfer.import_prefix``) and ``first_token`` was sampled by
        the prefill replica — map the imported blocks read-only and
        enter the batched decode step directly. NO prefill program runs
        on this replica (``serving.prefix.computed_tokens`` stays
        silent; tools/disagg_gate.py pins zero prefill dispatches).

        The first token re-emits HERE so the request's stream/handle
        carries the full sequence, and greedy decode from the imported
        rows is bit-identical to co-located serving. Raises
        :class:`HandoffError` (pool untouched) when the prefix is not
        fully resident or the replica has no slot/blocks — the caller
        fails open to co-located serving.

        ``trace_parent`` (a span ``context()`` dict off the prefill
        replica's ``serving.request`` root) stitches this stage's spans
        into the SAME cross-replica trace — including across a PROCESS
        boundary: a remote handoff (disagg._rpc_admit) ships the
        context in its admission rpc, so ``serving.decode_stage``
        genuinely spans hosts. ``transfer_us``/``transfer_bytes`` bill
        the fabric hop to this request's CostReport; ``handoff_id``
        (remote handoffs) rides the ``serving.handoff_admit`` span so
        the trace joins the lease/relay records."""
        if self._block_len > 1:
            raise HandoffError(
                "serving.admit_handoff: a block-diffusion model is not "
                "served disaggregated")
        if self.cache.state_spec is not None:
            raise HandoffError(
                "serving.admit_handoff: imported blocks hold K and V "
                "only; this model's recurrent state at the prompt's end "
                "exists on no replica but the one that prefilled it")
        if self.cache.latent_spec is not None:
            raise HandoffError(
                "serving.admit_handoff: no transfer frame carries latent "
                "rows, so none can have been imported")
        prompt = validate_request(prompt_ids, max_new_tokens,
                                  self.max_seq_len, self.cache,
                                  who="serving.admit_handoff")
        if not self.prefix_cache:
            raise HandoffError(
                "serving.admit_handoff: prefix cache disarmed — "
                "imported blocks cannot be admitted")
        plan = self.cache.plan_prefix(prompt)
        if plan.covered_tokens != plan.num_tokens:
            raise HandoffError(
                f"serving.admit_handoff: imported prefix covers "
                f"{plan.covered_tokens}/{plan.num_tokens} tokens")
        if len(self.running) >= self.cache.max_batch:
            raise HandoffError(
                "serving.admit_handoff: no free decode slot")
        slot = self.cache.alloc_slot_cached(plan)
        if slot is None:
            raise HandoffError(
                "serving.admit_handoff: out of slots/blocks")
        pri = _overload.NORMAL if priority is None else int(priority)
        req = ServingRequest(self._next_rid, prompt,
                             int(max_new_tokens), deadline=deadline,
                             on_token=on_token, on_finish=on_finish,
                             priority=pri)
        self._next_rid += 1
        # stitch into the prefill replica's trace when a context rode
        # the handoff; a fresh root otherwise (unsampled/off upstream)
        child = _tracing.span("serving.decode_stage",
                              parent=trace_parent, rid=req.rid,
                              prompt_len=len(prompt))
        req.span = child if child.recording else _tracing.start_trace(
            "serving.request", rid=req.rid, prompt_len=len(prompt),
            max_new_tokens=int(max_new_tokens), stage="decode")
        self.accounting.attach(req)
        self.accounting.note_transfer(req, transfer_us, transfer_bytes)
        req.status = RequestStatus.RUNNING
        req.slot = slot
        req.admit_seq = self._next_admit_seq
        self._next_admit_seq += 1
        req.admitted_at = time.monotonic()
        self.running[slot] = req
        _m_admitted.inc()
        # imported blocks fully cover the prompt: the decode step's
        # append lands at position len(prompt) (first_token's KV row),
        # exactly the state a local prefill would have left
        self.cache.seq_lens[slot] = plan.num_tokens
        self._last_tok[slot] = int(first_token)
        self._remaining[slot] = int(max_new_tokens) - 1
        _tracing.record_span("serving.handoff_admit", req.span, 0.0,
                             hit_blocks=plan.hit_blocks,
                             transfer_bytes=int(transfer_bytes),
                             **({"handoff_id": str(handoff_id)}
                                if handoff_id is not None else {}))
        self._emit(req, int(first_token))
        self._maybe_finish(slot)
        self._update_gauges()
        return req

    def cancel(self, req):
        """Request cancellation; takes effect (blocks freed, status
        CANCELLED, stream closed) at the next step boundary."""
        if not req.done:
            req.cancel_requested = True

    @property
    def has_work(self):
        return bool(self.queue or self.running
                    or self._flight is not None)

    def inflight(self):
        """Live (non-terminal) requests: queued + running — the number
        a drain must let finish (frontend lifecycle, /readyz body). A
        decode step still in flight with nobody left running (its slots
        finished on an EOS the step before) counts as one: a step has
        yet to read it."""
        return len(self.queue) + len(self.running) \
            or int(self._flight is not None)

    # -- the scheduling iteration -------------------------------------

    def step(self):
        """One iteration: sweep -> admit -> decode. Returns the list of
        (rid, token) emitted this step (prefill first tokens included;
        on the plain decode path the tokens of the decode step dispatched
        the step before, which this one read)."""
        # the step's number joins a request's prefill / decode_step
        # span to the phase spans of the step that ran it
        self._step_no = _m_steps.value
        with _phase("serving.step", step=self._step_no,
                    running=len(self.running), queued=len(self.queue)):
            t0 = time.monotonic()
            self.accounting.step_begin()
            with _phase("serving.sweep"):
                out = self._sweep()
            # overload control (serving/overload.py): pressure ->
            # brownout ladder update -> shed lowest-priority/newest
            # queued requests while over the watermarks — BEFORE
            # admission, so a step never prefills work it is about to
            # shed
            with _phase("serving.overload"):
                self.overload.control(self)
            with _phase("serving.admit"):
                out += self._admit()
            with _phase("serving.decode"):
                out += self._decode()
            _m_steps.inc()
            step_us = (time.monotonic() - t0) * 1e6
            _h_step.observe(step_us)
            with _phase("serving.step_end"):
                # apportion this step's wall time across the requests
                # that did work in it (profiler/accounting.py) BEFORE
                # the gauges so the capacity view and the attribution
                # agree on the step boundary
                self.accounting.step_end(step_us)
                self._update_gauges()
                if self.alerts is not None:
                    self.alerts.maybe_evaluate()
        return out

    def run_to_completion(self):
        """Drain everything; {rid: generated tokens} for ALL terminal
        requests (check .status for how each ended)."""
        while self.has_work:
            self.step()
        return {rid: req.generated
                for rid, req in self.finished.items()}

    # -- internals -----------------------------------------------------

    def _sweep(self):
        for req in list(self.queue):
            if req.cancel_requested:
                self.queue.remove(req)
                self._finish(req, RequestStatus.CANCELLED)
            elif req.deadline is not None and req.deadline.expired():
                self.queue.remove(req)
                self._expire(req)
        out = []
        for slot, req in list(self.running.items()):
            cancelled = req.cancel_requested
            if not cancelled and not (req.deadline is not None
                                      and req.deadline.expired()):
                continue
            # the token it has in flight was made before this boundary:
            # it is delivered first, as the in-order loop had delivered it
            out += self.land()
            if req.done:  # that token was its last
                continue
            if cancelled:
                self._finish(req, RequestStatus.CANCELLED)
            else:
                self._expire(req)
        return out

    def _expire(self, req):
        with _tracing.attach(req.span):  # flight record gets trace_id
            resilience.degrade("serving.deadline",
                               detail=f"rid={req.rid} "
                                      f"tokens={len(req.generated)}")
        self._finish(req, RequestStatus.TIMEOUT)

    def shed(self, req, retry_after_s=None):
        """Load-shed a QUEUED request (the overload controller's
        victim): terminal status SHED, blocks never allocated, handle
        closed with ``retry_after_s`` as the back-off hint. Survivors
        are untouched — shedding never changes a running request's
        schedule, so their greedy outputs stay bit-identical to an
        uncontended run (the preemption pin, extended)."""
        self.queue.remove(req)
        req.retry_after_s = retry_after_s
        _tracing.record_span("serving.shed", req.span, 0.0,
                             priority=req.priority,
                             queue_depth=len(self.queue))
        with _tracing.attach(req.span):  # flight record gets trace_id
            resilience.degrade(
                "serving.shed",
                detail=f"rid={req.rid} priority={req.priority} "
                       f"queue={len(self.queue)}")
        self._finish(req, RequestStatus.SHED)

    def _prefill_ids(self, req):
        # mirror of ContinuousBatchingEngine._prefill_ids — the
        # re-prefill contract (prefill of prompt+generated samples the
        # NEXT new token) must stay identical in both engines; each is
        # pinned against uncontended references by its own test file
        if not req.generated:
            return req.prompt
        return np.concatenate(
            [req.prompt,
             np.asarray(req.generated, dtype=req.prompt.dtype)])

    def _admit(self):
        """Strict FCFS: stop at the first request that doesn't fit (no
        head-of-line bypass — a small late prompt never jumps an older
        large one). Budgeted: cumulative prefill tokens per step stay
        under the budget, except the step's first admission, which is
        always allowed so an over-budget prompt still makes progress.

        Cache-aware: admission cost is the UNCOVERED tokens only — a
        request whose prefix is resident charges the budget for (and
        computes) just its tail, so cache-hitting requests admit cheaply
        and their TTFT collapses to a near-no-op. Hashing/planning works
        on the raw ids; bucket padding happens after and never reaches a
        chunk hash (serving/bucketing.py)."""
        out = []
        used = 0
        budget = self.prefill_token_budget
        bs = self.cache.block_size
        while self.queue:
            if len(self.running) >= self.cache.max_batch:
                break  # before planning: don't hash prompts every
                #        decode step while the batch stays full
            with _phase("serving.admit.plan"):
                req = self.queue[0]
                ids = self._prefill_ids(req)
                given = ids[:0]
                if self._block_len > 1:
                    # whole blocks are prefilled; what is left over of
                    # the prompt opens the first block unmasked
                    whole = len(ids) // self._block_len * self._block_len
                    ids, given = ids[:whole], ids[whole:]
                ids_len = len(ids)
                plan = self.cache.plan_prefix(ids) \
                    if self.prefix_cache and ids_len else None
                covered = plan.covered_tokens if plan is not None else 0
                # full coverage still computes the final token for its
                # logits; everything covered is free
                uncovered = max(ids_len - covered, 1)
                if used > 0 and budget and used + uncovered > budget:
                    break
                slot = self.cache.alloc_slot_cached(plan) \
                    if plan is not None else self.cache.alloc_slot(ids_len)
                if slot is None:
                    break
                self.queue.pop(0)
                used += uncovered
                req.slot = slot
                req.status = RequestStatus.RUNNING
                req.admit_seq = self._next_admit_seq
                self._next_admit_seq += 1
                now = time.monotonic()
                if req.admitted_at is None:
                    req.admitted_at = now
                    wait_us = (now - req.submitted_at) * 1e6
                    with _tracing.attach(req.span):  # exemplar -> trace
                        _h_queue_wait.observe(wait_us)
                    _tracing.record_span("serving.queue_wait", req.span,
                                         wait_us)
                    self.accounting.note_queue_wait(req, wait_us)
                self.running[slot] = req
                _m_admitted.inc()
            comp0 = _compile_s()  # compile billed to THIS request
            saved0 = _saved_s()   # ...and so are AOT-cache savings
            t_pf = time.perf_counter_ns()
            # the prefill is dispatched behind the decode step in flight
            # and waits for what is left of it
            wait_us = self._left_of_flight(t_pf)
            if self._block_len > 1:
                tok = None  # a block-diffusion prefill samples nothing
                pad_to = self._prefill_blocks(req, slot, ids, plan)
            elif covered:
                tail_start = plan.tail_start
                pad_to = bucket_length(ids_len - tail_start, bs,
                                       self.bucket_cap,
                                       max_len=self.max_seq_len)
                with _tracing.span("serving.prefill", parent=req.span,
                                   tokens=ids_len, pad_to=pad_to,
                                   reprefill=bool(req.generated),
                                   covered=covered,
                                   hit_blocks=plan.hit_blocks,
                                   step=self._step_no):
                    tok = int(self.model.paged_prefill_extend(
                        self.cache, slot, ids, tail_start,
                        plan.write_start,
                        temperature=self.temperature, pad_to=pad_to,
                        **self.prefill_route))
            else:
                pad_to = bucket_length(ids_len, bs, self.bucket_cap,
                                       max_len=self.max_seq_len)
                with _tracing.span("serving.prefill", parent=req.span,
                                   tokens=ids_len, pad_to=pad_to,
                                   reprefill=bool(req.generated),
                                   covered=0, hit_blocks=0,
                                   step=self._step_no):
                    tok = int(self.model.paged_prefill(
                        self.cache, slot, ids,
                        temperature=self.temperature, pad_to=pad_to,
                        **self.prefill_route))
            pf_us = (time.perf_counter_ns() - t_pf) / 1000.0
            with _phase("serving.admit.finish"):
                comp_us = (_compile_s() - comp0) * 1e6
                if plan is not None:
                    _m_prefix_computed.inc(pad_to)
                    self.cache.commit_prefix(slot, plan)
                if tok is None:
                    self.accounting.note_prefill(
                        req, pad_to, covered, comp_us,
                        reprefill=req.preempts > 0,
                        aot_saved_us=(_saved_s() - saved0) * 1e6,
                        emitted=0)
                    if pad_to:
                        self.overload.observe_prefill(
                            pad_to, max(pf_us - comp_us, 0.0))
                    self._remaining[slot] = \
                        req.max_new_tokens - len(req.generated)
                    self._open_block(slot, given)
                    continue
                # the prefill note carries only the COMPUTED (padded
                # tail) tokens — covered prefix tokens are free in the
                # apportionment, re-prefill bills to the preemption event
                self.accounting.note_prefill(
                    req, pad_to, covered, comp_us,
                    reprefill=req.preempts > 0,
                    aot_saved_us=(_saved_s() - saved0) * 1e6)
                # the admission model's EWMA sees the COMPILE-FREE cost
                # per computed token — a cold bucket's compile must not
                # poison the steady-state service-time estimate
                self.overload.observe_prefill(
                    pad_to, max(pf_us - comp_us - wait_us, 0.0))
                self._last_tok[slot] = tok
                self._remaining[slot] = \
                    req.max_new_tokens - len(req.generated) - 1
                if req.prefill_only:
                    # disagg prefill stage: stop at the first token —
                    # the decode stage continues from the handed-off
                    # blocks on another replica (serving/disagg.py)
                    self._remaining[slot] = 0
                self._emit(req, tok)
                out.append((req.rid, tok))
                self._maybe_finish(slot)
        return out

    def _choose_victim(self):
        """Victim choice: with the overload plane armed, lowest
        priority first, newest within a class (equal priorities reduce
        to the legacy newest-admitted order, so default-priority
        traffic is byte-for-byte unchanged); disarmed, pure
        newest-admitted (FCFS holds). Either way reclaimability-aware:
        preempting a request whose blocks are all SHARED frees
        nothing — skip past such victims to the first one whose
        eviction actually returns blocks to the pool."""
        if self.overload.shedding:
            key = lambda s: (-self.running[s].priority,  # noqa: E731
                             -self.running[s].admit_seq)
        else:
            key = lambda s: -self.running[s].admit_seq  # noqa: E731
        cands = sorted(self.running, key=key)
        for s in cands:
            if self.cache.reclaimable_blocks(s) > 0:
                return s
        return cands[0]

    def _runs_next(self, slot):
        """Whether the slot is in the next decode step: not when the
        tokens it has left are those the step in flight will hand it."""
        flight = self._flight
        if flight is None or flight.reqs.get(slot) is not self.running[slot]:
            return self._remaining[slot] > 0
        return self._remaining[slot] > flight.owed[slot]

    def _make_writable(self, grow, landed=None):
        """Make the next ``grow`` positions writable (1: a token; a
        block's length: an open block, every step) of each running slot
        that is in the next step: grow tables (cold cached prefixes are
        LRU-evicted before anything else — eviction always runs before
        preemption), copy-on-write shared blocks; preempt a victim on
        true pool exhaustion (never truncate). A preemption requeues
        prompt + generated, which must hold the token in flight: the
        step in flight is read first (its tokens go to ``landed``), and
        the blocks of those it finished may do."""
        for slot in list(self.running):
            while slot in self.running and (
                    self._flight is None or self._runs_next(slot)):
                new_len = int(self.cache.seq_lens[slot]) + grow
                denied = self.cache.prepare_append(slot, new_len) \
                    if grow == 1 else \
                    self.cache.prepare_append_range(slot, new_len)
                if denied:
                    break
                if denied.reason == CapacityError.SEQ_LIMIT:
                    # retrying can never help — only a caller
                    # bypassing validate_request's worst-case bound
                    # can get here
                    req = self.running[slot]
                    raise RuntimeError(
                        f"serving: request {req.rid} outgrew "
                        f"max_blocks_per_seq: {denied.detail}")
                if self._flight is not None:
                    landed += self.land()
                    continue
                if len(self.running) == 1:
                    # unreachable since validate_request bounds each
                    # request's worst-case demand to the pool; keep
                    # as an invariant guard
                    req = self.running[slot]
                    need = math.ceil(new_len / self.cache.block_size)
                    raise RuntimeError(
                        f"serving: KV pool exhausted — request "
                        f"{req.rid} needs {need} blocks, pool has "
                        f"{self.cache.num_blocks - 1} usable and no "
                        "other running request to preempt; increase "
                        "num_blocks or lower max_seq_len")
                victim = self._choose_victim()
                self._preempt(victim)
                if victim == slot:
                    break  # grower preempted itself; re-prefills later

    def _dispatch_decode(self, dispatch, batch, ctx_tokens, **stats):
        """Dispatch one batched decode program under the shared
        instrumentation contract: the dispatch a phase, compile +
        AOT-saved deltas billed through the accountant, whether the step
        before was still unread counted. ``dispatch`` returns the
        program's tokens still on the device; ``stats`` join ``batch``
        and ``context_tokens`` on the dispatch span. Returns the
        :class:`_Flight` that ``_await_tokens`` reads."""
        (_m_in_order if self._flight is None else _m_ahead).inc()
        comp0 = _compile_s()
        saved0 = _saved_s()
        t_ns = time.perf_counter_ns()
        with _phase("serving.decode.dispatch", batch=batch,
                    context_tokens=ctx_tokens, **stats):
            toks = dispatch()
        _m_ctx_tokens.inc(ctx_tokens)
        comp_us = (_compile_s() - comp0) * 1e6
        saved_us = (_saved_s() - saved0) * 1e6
        self.accounting.note_decode_compile(comp_us)
        self.accounting.note_decode_aot_saved(saved_us)
        return _Flight(toks, self._step_no, t_ns, comp_us,
                       built=comp_us > 0.0 or saved_us > 0.0)

    def _await_tokens(self, flight):
        """The read-back that waits for a dispatched step's tokens, a
        phase of its own. Returns (tokens as numpy, us the step took as
        the host can see it): from the later of its dispatch and the
        arrival of the step before to the arrival of its own tokens —
        in order that is the wall time of dispatch + read-back, one step
        ahead the time between two arrivals. Compile excluded, it feeds
        overload control's service-time estimate, unless a prefill was
        read back in between (``_left_of_flight``)."""
        with _phase("serving.decode.readback"):  # waits for the device
            toks = np.asarray(flight.toks)
        now = time.perf_counter_ns()
        dec_us = (now - max(flight.t_ns, self._seen_ns)) / 1000.0
        self._seen_ns = now
        if flight.timed:
            self._dec_us = max(dec_us - flight.comp_us, 0.0)
            self.overload.observe_decode(self._dec_us)
        return toks, dec_us

    def _left_of_flight(self, at_ns):
        """Microseconds the decode step in flight still had to run at
        ``at_ns``, by the last step time the host saw: a prefill
        dispatched behind it waits that long on the device, which is not
        the prefill's cost. The prefill's read-back then hides when the
        step's tokens arrived, so that step feeds the service-time
        estimate nothing. 0 with nothing in flight."""
        flight = self._flight
        if flight is None:
            return 0.0
        flight.timed = False
        if self._dec_us is None:
            return 0.0
        ran_us = (at_ns - max(flight.t_ns, self._seen_ns)) / 1000.0
        return max(self._dec_us - ran_us, 0.0)

    def _timed_decode_dispatch(self, dispatch, batch, ctx_tokens,
                               **stats):
        """One decode program dispatched and read in order
        (``_dispatch_decode`` + ``_await_tokens``): the speculative
        path, whose next input is made on the host. Returns (tokens as
        numpy, wall us of both)."""
        return self._await_tokens(
            self._dispatch_decode(dispatch, batch, ctx_tokens, **stats))

    def _decode(self):
        """The plain and the block path run one step ahead. Who is in
        step K+1 is known from the counts before step K's tokens are
        read, and its token input *is* step K's output (a block step
        returns the open blocks for the next): so K+1 is dispatched with
        that array, still on the device (``_launch``,
        ``_launch_block``), and only then are K's tokens read, emitted
        and its finished requests freed (``land``) — while the device
        runs K+1. In order is the same code with the read before the
        next dispatch: whatever must see every token on the host first
        calls ``land`` (a preemption, a swept running request), and a
        step whose next input is made on the host (speculation's drafts)
        or that built its program is read as soon as it is dispatched."""
        if not self.running:
            return self.land()
        if self.spec:
            out = self._decode_spec()
            if out is not None:
                return out
            # nothing proposed (or speculative capacity unavailable):
            # this step runs the plain single-token path below —
            # bit-equivalent, just not multiplied
        out = []
        flight = self._launch_block(out) if self._block_len > 1 \
            else self._launch(out)
        out += self.land()
        self._flight = flight
        if flight is not None and (self.spec or flight.built):
            out += self.land()
        return out

    def _launch(self, landed):
        """Prepare and dispatch the next plain decode step from host
        state alone; None when no running slot has a token left to make.
        Tokens of a step it had to read first go to ``landed``."""
        with _phase("serving.decode.prepare"):
            self._make_writable(1, landed)
            live = [s for s in self.running if self._runs_next(s)]
            if not live:
                return None
            active = np.zeros((self.cache.max_batch,), bool)
            active[live] = True
            batch = len(live)
            ctx_tokens = int(self.cache.seq_lens[active].sum()) + batch
        stats, probe = {}, {}
        if self.cache.state_spec is not None:
            # the live slots are the ones whose state the step updates
            stats["state_slots"] = batch
            _m_state_slot_steps.inc(batch)
        if self._observed:
            probe["state_observer"] = lambda: self.state_observer
        # decode compiles split across the batch
        flight = self._dispatch_decode(
            lambda: self.model.paged_decode_step(
                self.cache,
                self._token_input(live, lambda: np.asarray(self._last_tok)),
                active, temperature=self.temperature,
                kernel_mode=self.kernel_mode, **probe),
            batch, ctx_tokens, **stats)
        flight.reqs = {s: self.running[s] for s in live}
        flight.owed = active  # one token a live slot
        return flight

    def _token_input(self, live, host):
        """The token input of the step over the slots ``live``: the
        host's (``host()``: the last tokens, or a block-diffusion
        model's open blocks) with nothing in flight; else what the step
        in flight returned for the next, still on the device, with the
        slots admitted since it was dispatched (their first token, or
        their open block, is on the host, from their admission) put in
        there."""
        prev = self._flight
        if prev is None:
            return host()
        fresh = [s for s in live
                 if prev.reqs.get(s) is not self.running[s]]
        if not fresh:
            return prev.feed
        mask = np.zeros((self.cache.max_batch,), bool)
        mask[fresh] = True
        return merge_tokens(prev.feed, host(), mask)

    def land(self):
        """Read the decode step in flight, if there is one: its tokens
        reach the host, are emitted (a block step's: those of the blocks
        it committed), and the requests whose count ran out (or that
        emitted EOS) finish. Returns the (rid, token) list.
        The loop calls it after dispatching the next step; whoever needs
        every running request's tokens on the host calls it first (a
        preemption, a cancellation, a test's in-order reference)."""
        flight, self._flight = self._flight, None
        if flight is None:
            return []
        toks, dec_us = self._await_tokens(flight)
        with _phase("serving.decode.emit"):
            out = self._emit_tokens(flight, toks, dec_us) \
                if flight.block is None \
                else self._emit_blocks(flight, toks, dec_us)
        _m_decoded.inc(len(out))
        return out

    def _emit_tokens(self, flight, toks, dec_us):
        """What ``land`` does with a plain decode step's tokens."""
        out = []
        if self._expert_rows is not None:
            _count_expert_rows(self._expert_rows(toks))
        for slot, req in flight.reqs.items():
            if self.running.get(slot) is not req:
                # it emitted EOS in the step before, after this one
                # was dispatched: this token is dropped
                continue
            t = int(toks[slot])
            self._last_tok[slot] = t
            self._remaining[slot] -= 1
            # the decode dispatch is one batched program: each live
            # request's trace gets a slice of that step's wall time
            _tracing.record_span("serving.decode_step", req.span,
                                 dec_us, token=len(req.generated),
                                 batch=len(flight.reqs),
                                 route=self.kernel_route,
                                 step=flight.step_no)
            self.accounting.note_decode(req)
            self._emit(req, t)
            out.append((req.rid, t))
            self._maybe_finish(slot)
        return out

    # -- block-diffusion decoding (docs/SERVING.md) ---------------------

    def _prefill_blocks(self, req, slot, ids, plan):
        """Prefill ``ids`` (whole blocks) of a block-diffusion request
        into ``slot``: the plain program, the tail-extend program after
        a prefix hit, or nothing where the cache covers all of it (no
        logits are wanted of a prefill). Returns the padded tokens
        computed."""
        n = len(ids)
        covered = plan.covered_tokens if plan is not None else 0
        if covered == n:
            self.cache.seq_lens[slot] = n
            return 0
        pad_to = bucket_length(n - covered, self.cache.block_size,
                               self.bucket_cap, max_len=self.max_seq_len)
        with _tracing.span("serving.prefill", parent=req.span, tokens=n,
                           pad_to=pad_to, reprefill=bool(req.generated),
                           covered=covered,
                           hit_blocks=plan.hit_blocks if covered else 0,
                           step=self._step_no):
            if covered:
                self.model.paged_prefill_extend(
                    self.cache, slot, ids, covered, covered,
                    pad_to=pad_to, kernel_mode=self.kernel_mode)
            else:
                self.model.paged_prefill(
                    self.cache, slot, ids, pad_to=pad_to,
                    kernel_mode=self.kernel_mode)
        return pad_to

    def _open_block(self, slot, given):
        """Open an admitted slot's first block at ``seq_len``, on the
        host: ``given`` ids (what a prompt leaves over past its last
        whole block) unmasked, the rest masked. The next launch puts it
        in on the device."""
        g = len(given)
        self._blk_ids[slot, :g] = given
        self._blk_ids[slot, g:] = self.model.config.mask_token_id
        self._blk_masked[slot] = np.arange(self._block_len) >= g
        self._blk_given[slot] = g
        self._blk_denoised[slot] = 0

    def _launch_block(self, landed):
        """Prepare and dispatch the next block step (``models/sdar.py``)
        from host counts alone; None when no running slot has a token
        left to make. ONE forward over every such slot's open block,
        whichever phase it is in. With a static schedule the counts say
        which: a block that opened with M masked positions takes min(M,
        ``denoise_steps``) denoising forwards, in which the rule (inside
        the program) unmasks some positions and nothing is emitted, and
        then one commit forward, which writes the block's final keys and
        values: ``seq_len`` moves past it here, at dispatch, and the next
        block opens masked. Which positions were unmasked, and to which
        tokens, only the device knows until ``land`` reads the step: the
        open blocks are the program's own output fed back
        (``_token_input``). Tokens of a step it had to read first go to
        ``landed``."""
        width = self._block_len
        with _phase("serving.decode.prepare"):
            # the open block's rows are rewritten every step
            self._make_writable(width, landed)
            live = [s for s in self.running if self._runs_next(s)]
            if not live:
                return None
            active = np.zeros((self.cache.max_batch,), bool)
            active[live] = True
            opened = width - self._blk_given
            denoise = active & (self._blk_denoised < np.minimum(
                opened, int(self.model.config.denoise_steps)))
            commit = active & ~denoise
            batch = len(live)
            n_denoise = int(denoise.sum())
            lens = self.cache.seq_lens
            ctx_tokens = int(lens[active].sum()) + batch * width
            state = self._token_input(live, lambda: self.model.block_state(
                self._blk_ids, self._blk_masked, opened,
                self._blk_denoised))
        # read once: another thread may take the observer away mid-step.
        # It is also shown what each expert layer saw and gave
        observer = self.block_observer
        moe = [] if observer is not None else None
        flight = self._dispatch_decode(
            lambda: self.model.paged_block_step(
                self.cache, state, active, kernel_mode=self.kernel_mode,
                moe_sink=moe),
            batch, ctx_tokens, rows=batch * width,
            denoise_slots=n_denoise, commit_slots=batch - n_denoise)
        flight.toks, flight.feed = flight.toks
        flight.reqs = {s: self.running[s] for s in live}
        # a commit hands out the block's tokens past those the prompt
        # gave, up to the request's count
        flight.owed = np.where(
            commit, np.minimum(opened, self._remaining), 0)
        # what ``_emit_blocks`` needs of this step as it was launched:
        # who commits, the positions each block opened with, the
        # committed lengths the forward ran at (the array the program was
        # handed: nothing writes it again), the observer and its arrays
        flight.block = (commit, opened, lens, batch, observer,
                        moe[0] if moe else None)
        # the counts move on now: the next launch is made from them
        self._blk_denoised = np.where(commit, 0,
                                      self._blk_denoised + denoise)
        self._blk_given = np.where(commit, 0, self._blk_given)
        self.cache.seq_lens = np.where(commit, lens + width,
                                       lens).astype(np.int32)
        return flight

    def _emit_blocks(self, flight, packed, dec_us):
        """What ``land`` does with a block step: the counters, the
        observer's records, the host's copy of the open blocks, and for
        every slot whose block the step committed its tokens, emitted
        together (those the prompt gave, and those past
        ``max_new_tokens``, are not). Every id is the device's own: what
        the forward was fed and what the rule did rides the read-back."""
        width = self._block_len
        commit, opened, lens, batch, observer, moe = flight.block
        got = self.model.unpack_block_step(packed, self.cache.max_batch)
        expert_rows, picked = got["expert_rows"], got["unmasked"]
        _count_expert_rows(expert_rows)
        # a slot that emitted EOS in the step before, after this one was
        # dispatched, ran in it for nothing: its result is dropped
        mine = [slot for slot, req in flight.reqs.items()
                if self.running.get(slot) is req]
        kept = np.zeros((self.cache.max_batch,), bool)
        kept[mine] = True
        commits = kept & commit
        n_commit = int(commits.sum())
        _m_blk_denoise.inc(len(mine) - n_commit)
        _m_blk_commit.inc(n_commit)
        _m_blk_unmasked.inc(int(picked[kept].sum()))
        if observer is not None:
            for slot in mine:
                observer({
                    "rid": flight.reqs[slot].rid, "step": flight.step_no,
                    "seq_len": int(lens[slot]),
                    "ids": got["ids"][slot], "masked": got["masked"][slot],
                    "commit": bool(commit[slot]),
                    "tokens": got["tokens"][slot],
                    "logits": got["logits"][slot],
                    "probs": got["probs"][slot], "unmasked": picked[slot],
                    # of the whole step: the slots live in it, the
                    # rows each expert of each layer got, and the
                    # expert layers' (input, output) and (router's
                    # weights, expert ids), two device arrays [2,
                    # layers, rows, .] whose rows ``moe_rows`` are
                    # this slot's
                    "batch": batch, "expert_rows": expert_rows,
                    "moe": moe,
                    "moe_rows": slice(slot * width, (slot + 1) * width)})
        # the open blocks after this step, for a launch with nothing in
        # flight: the rule's picks filled in, a committed block's
        # successor all masked
        self._blk_ids[kept] = np.where(
            commits[:, None], self.model.config.mask_token_id,
            np.where(picked, got["tokens"], got["ids"]))[kept]
        self._blk_masked[kept] = (
            commits[:, None] | (got["masked"] & ~picked))[kept]
        out = []
        with _phase("serving.block.commit"):
            # plain lists: this loop runs for every slot every step
            commits = commits.tolist()
            given = (width - opened).tolist()
            remaining = self._remaining.tolist()
            for slot in mine:
                req = flight.reqs[slot]
                _tracing.record_span(
                    "serving.decode_step", req.span, dec_us,
                    token=len(req.generated), batch=batch,
                    route=self.kernel_route, step=flight.step_no,
                    commit=commits[slot])
                if not commits[slot]:
                    self.accounting.note_block(req, width, 0)
                    continue
                new = got["ids"][slot, given[slot]:].tolist()
                emitted = 0
                for t in new[:remaining[slot]]:
                    emitted += 1
                    self._emit(req, t)
                    out.append((req.rid, t))
                    if t == self.eos_token_id:
                        break
                self._remaining[slot] -= emitted
                self.accounting.note_block(req, width, emitted)
                self._maybe_finish(slot)
            _m_blk_blocks.inc(n_commit)
        return out

    def _decode_spec(self):
        """One speculative decode iteration (docs/SERVING.md "Decode
        speed tiers"): propose up to ``spec_tokens`` draft tokens per
        running request from its OWN context (prompt-lookup n-grams,
        serving/spec.py), verify all of them in ONE batched
        multi-position paged sweep (``Llama.paged_spec_step``), accept
        the longest greedy-matching prefix per request, and roll
        rejected rows' blocks back. Greedy outputs are bit-identical
        to plain decode because every emitted token IS the sweep's own
        argmax — drafts only decide how many of those argmaxes one
        step may keep.

        Returns the (rid, token) list, or None to fall back to the
        plain path for this step: nothing proposed anywhere, or the
        pool cannot hold the speculative rows right now (the plain
        path then evicts/preempts its way forward; speculation simply
        re-engages when space returns — preemption and prefix hits
        compose, test-pinned)."""
        k = self.spec_tokens
        bs = self.cache.block_size
        with _phase("serving.decode.prepare"):
            drafts = {}
            any_proposed = False
            for slot, req in self.running.items():
                cap = min(k, int(self._remaining[slot]) - 1)
                d = _spec.propose_draft(self._prefill_ids(req), cap,
                                        self.spec_ngram) \
                    if cap > 0 else np.empty((0,), np.int64)
                drafts[slot] = d
                any_proposed = any_proposed or d.size > 0
            if not any_proposed:
                return None
            # capacity: every slot needs positions [len, len + 1 +
            # drafts) writable (growth + COW of every touched shared
            # block). Track pre-grow block counts so a mid-loop failure
            # rolls EVERY slot back — the plain path must start from an
            # untouched table.
            grown = []
            failed = None
            for slot in list(self.running):
                old = len(self.cache._slot_blocks[slot])
                need = int(self.cache.seq_lens[slot]) + 1 + \
                    int(drafts[slot].size)
                r = self.cache.prepare_append_range(slot, need)
                if not r:
                    failed = r
                    break
                grown.append((slot, old))
            if failed is not None:
                for slot, old in grown:
                    self.cache.truncate_blocks(slot, old)
                return None
            draft_mat = np.zeros((self.cache.max_batch, k), np.int64)
            n_inputs = np.zeros((self.cache.max_batch,), np.int64)
            active = np.zeros((self.cache.max_batch,), bool)
            for slot, d in drafts.items():
                active[slot] = True
                n_inputs[slot] = 1 + d.size
                draft_mat[slot, :d.size] = d
            # every candidate row is written, then attended with the
            # slot's whole context
            ctx_tokens = int(self.cache.seq_lens[active].sum()
                             + n_inputs.sum())

        outs, dec_us = self._timed_decode_dispatch(
            lambda: self.model.paged_spec_step(
                self.cache, np.asarray(self._last_tok), draft_mat,
                n_inputs, active),
            len(drafts), ctx_tokens)
        out = []
        with _phase("serving.decode.emit"):
            for slot, req in list(self.running.items()):
                g = outs[slot]
                proposed = int(drafts[slot].size)
                # accept while each draft equals the model's own previous
                # argmax — then the emitted run is g[0..m], exactly what
                # m+1 sequential steps would have produced
                m = 0
                while m < proposed and \
                        int(draft_mat[slot, m]) == int(g[m]):
                    m += 1
                emitted = [int(g[i]) for i in range(m + 1)]
                if self.eos_token_id is not None:
                    for j, t in enumerate(emitted):
                        if t == self.eos_token_id:
                            # sequential decode stops here: later
                            # accepted rows must not survive
                            emitted = emitted[:j + 1]
                            m = j
                            break
                # inputs consumed = len(emitted) (last_tok + m drafts):
                # their KV rows are exactly the ones sequential decode
                # would have written; roll the rest back
                new_seq = int(self.cache.seq_lens[slot]) + len(emitted)
                self.cache.seq_lens[slot] = new_seq
                self.cache.truncate_blocks(
                    slot, max(math.ceil(new_seq / bs), 1))
                self._last_tok[slot] = emitted[-1]
                self._remaining[slot] -= len(emitted)
                _m_spec_proposed.inc(proposed)
                _m_spec_accepted.inc(m)
                _m_spec_rejected.inc(proposed - m)
                if proposed:
                    with _tracing.attach(req.span):  # exemplar -> trace
                        _h_spec_accept.observe(m / proposed)
                _tracing.record_span("serving.decode_step", req.span,
                                     dec_us, token=len(req.generated),
                                     batch=len(self.running),
                                     route=self.kernel_route,
                                     spec_proposed=proposed,
                                     spec_accepted=m, step=self._step_no)
                if proposed:
                    self.accounting.note_spec(req, emitted=len(emitted),
                                              proposed=proposed,
                                              accepted=m)
                else:
                    self.accounting.note_decode(req)
                for t in emitted:
                    self._emit(req, t)
                    out.append((req.rid, t))
                self._maybe_finish(slot)
        _m_spec_steps.inc()
        _m_decoded.inc(len(out))
        return out

    def _preempt(self, slot):
        """Free the victim's slot + blocks; requeue at the FRONT for
        re-prefill (prompt + generated) once pages free up. Greedy
        decode continues identically — pinned by test_serving.py."""
        req = self.running.pop(slot)
        self.cache.free_slot(slot)
        req.slot = -1
        req.status = RequestStatus.QUEUED
        req.preempts += 1
        self.queue.insert(0, req)
        _m_preempt.inc()
        _tracing.record_span("serving.preempt", req.span, 0.0,
                             generated=len(req.generated),
                             preempts=req.preempts)
        with _tracing.attach(req.span):  # flight record gets trace_id
            resilience.degrade(
                "serving.preempt",
                detail=f"rid={req.rid} "
                       f"len={len(req.prompt) + len(req.generated)}")

    def _emit(self, req, tok):
        req.generated.append(tok)
        now = time.monotonic()
        # SLO observations run under the request's trace context so the
        # histogram exemplar retained for the bucket names THIS trace
        with _tracing.attach(req.span):
            if req.first_token_at is None:
                req.first_token_at = now
                _h_ttft.observe((now - req.submitted_at) * 1e6)
            else:
                _h_itl.observe((now - req.last_token_at) * 1e6)
        req.last_token_at = now
        if req.on_token is not None:
            try:
                req.on_token(req, tok)
            except Exception:  # noqa: BLE001 — user cb must not kill serving
                _m_cb_errors.inc()

    def _maybe_finish(self, slot):
        req = self.running.get(slot)
        if req is None:
            return
        if self._remaining[slot] <= 0 or (
                self.eos_token_id is not None and req.generated
                and req.generated[-1] == self.eos_token_id):
            self._finish(req, RequestStatus.DONE)

    def _finish(self, req, status):
        if req.slot >= 0:
            self.cache.free_slot(req.slot)
            self.running.pop(req.slot, None)
            req.slot = -1
        req.status = status
        self.accounting.on_finish(req, status)
        _tracing.record_span("serving.terminal", req.span, 0.0,
                             terminal=status,
                             tokens=len(req.generated))
        req.span.annotate(terminal=status, tokens=len(req.generated),
                          preempts=req.preempts)
        req.span.end(status)
        self.finished[req.rid] = req
        {RequestStatus.DONE: _m_done,
         RequestStatus.CANCELLED: _m_cancelled,
         RequestStatus.TIMEOUT: _m_timeout,
         RequestStatus.SHED: _m_shed,
         RequestStatus.ERROR: _m_errors}[status].inc()
        if req.on_finish is not None:
            try:
                req.on_finish(req)
            except Exception:  # noqa: BLE001
                _m_cb_errors.inc()

    def fail_all(self, exc=None):
        """Engine died: terminate every live request with ERROR so no
        consumer blocks forever (the frontend re-raises the cause)."""
        try:
            self.land()  # what the last step made is delivered first
        except Exception:  # noqa: BLE001 — the program that died made it
            pass
        for req in list(self.queue):
            self._finish(req, RequestStatus.ERROR)
        self.queue.clear()
        for slot in list(self.running):
            self._finish(self.running[slot], RequestStatus.ERROR)
        self._update_gauges()

    def _update_gauges(self):
        usable = self.cache.num_blocks - 1
        # num_free_blocks counts reclaimable cached blocks as free, so
        # blocks_used is blocks pinned by LIVE requests (refcount > 0)
        used = usable - self.cache.num_free_blocks()
        _g_queue.set(len(self.queue))
        _g_running.set(len(self.running))
        _g_blocks.set(used)
        _g_util.set(round(used / usable, 4) if usable else 0.0)
        _g_shared.set(self.cache.num_shared_blocks())
        _g_cached.set(self.cache.num_cached_blocks())
        # mesh-armed engines also publish the per-slice breakdown
        # (slice-labeled gauges; per-slice sums == the aggregates
        # above, pinned by tests/framework/test_mesh_serving.py)
        if self._slice_gauges:
            for i, occ in enumerate(self.cache.occupancy_slices()):
                g = self._slice_gauges[i]
                g["active_blocks"].set(occ["active"])
                g["free_blocks"].set(occ["free"])
                g["shared_blocks"].set(occ["shared"])
                g["cached_blocks"].set(occ["cached_free"])
        # armed accounting also keeps the occupancy-breakdown gauges
        # (active/free/pool-bytes) + throttled HBM sampling fresh
        self.accounting.update_capacity(self.cache)
