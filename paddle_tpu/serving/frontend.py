"""Thread-safe serving frontend: submit/stream/cancel over the
iteration scheduler.

``ServingEngine`` is the process-wide entry point a server loop (RPC
handler, HTTP worker pool, ...) calls from many threads:

- ``submit() -> RequestHandle`` — validated admission; the handle
  streams tokens incrementally (``stream()`` iterator, ``on_token``
  callback), waits for completion (``result()``), and cancels.
- a background **driver thread** (default) runs scheduler steps while
  work exists and sleeps on a condition otherwise; ``background=False``
  hands the stepping to the caller (``step()`` / ``run_until_idle()``)
  for deterministic tests and gates.
- per-request deadlines ride on ``core.resilience.Deadline``; expired
  requests finish with status ``TIMEOUT`` at the next step boundary.
- an explicit **lifecycle** (``WARMING -> READY -> DRAINING ->
  CLOSED``) served from ``/readyz`` — distinct from ``/healthz``
  liveness. ``submit()`` is accepted ONLY in READY: a WARMING engine
  rejects with ``NotReadyError`` exactly like a DRAINING one, so a
  request can never be billed a cold compile that ``warmup()`` should
  have paid — ``/readyz`` and submit semantics agree. ``warmup()``
  precompiles the bounded serving program set (every prefill bucket +
  the decode step; with the AOT cache armed this loads-or-stores
  serialized executables, so the NEXT process boots zero-compile)
  and flips WARMING -> READY. A graceful ``drain()``: admission
  stops (``NotReadyError``), every in-flight request finishes with
  its terminal status unchanged and outputs bit-identical to an
  undrained run, readiness flips, and the replica deregisters from
  the fleet registry (profiler/fleet.py). This is the drain contract
  the multi-replica router (serving/router.py) rolls deploys
  against (docs/SERVING.md).

One re-entrant lock guards all scheduler state, and the driver holds it
for the duration of a scheduling iteration (prefill + decode are device
calls) — so ``submit()``/``cancel()``/``tokens()`` are cheap host-side
operations that may nevertheless wait up to one in-flight step (or a
cold compile, on the very first requests) before acquiring the lock.
Don't call them on a thread that cannot tolerate ~one decode step of
latency. If the driver thread dies, every live request terminates with
``ERROR`` and the cause re-raises from ``submit``/``result`` — a
crashed engine never leaves a consumer blocked on a silent stream.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time

import numpy as np

from ..core import flags as flags_mod
from ..core import resilience
from ..profiler import metrics as _metrics
from ..profiler import tracing as _tracing
from .bucketing import bucket_lengths
from .scheduler import (AdmissionRejected, HandoffError, merge_tokens,
                        QueueFullError, RequestStatus, Scheduler)

__all__ = ["ServingEngine", "RequestHandle", "QueueFullError",
           "AdmissionRejected", "RequestStatus", "Lifecycle",
           "NotReadyError", "HandoffError"]

# replica roles (disaggregated serving, serving/disagg.py): the fleet
# registry carries the role so a stage-aware router can rank prefill
# and decode candidates separately; "mixed" (the default) serves both
# stages co-located — existing fleets are untouched
ROLES = ("mixed", "prefill", "decode")

_SENTINEL = object()


class Lifecycle:
    """Replica readiness states (/readyz; docs/SERVING.md "Drain
    contract" / "Cold start & routing"): WARMING precompiles and
    rejects submits (``warmup()`` -> READY); READY is routable;
    DRAINING finishes in-flight work while rejecting new submits;
    CLOSED is terminal."""

    WARMING = "WARMING"
    READY = "READY"
    DRAINING = "DRAINING"
    CLOSED = "CLOSED"


class NotReadyError(RuntimeError):
    """Submission rejected because the engine is not READY (WARMING,
    DRAINING, or CLOSED) — the caller should route to another replica
    (or finish ``warmup()`` first)."""


_c_drain_started = _metrics.counter("serving.drain.started")
_c_drain_completed = _metrics.counter("serving.drain.completed")
_c_warmup_programs = _metrics.counter("serving.warmup.programs")
_h_warmup_us = _metrics.histogram(
    "serving.warmup_us",
    bounds=(10000, 100000, 500000, 1000000, 5000000, 30000000))
_g_lifecycle_ready = _metrics.gauge("serving.lifecycle.ready")


class RequestHandle:
    """Caller-side view of one request. Safe to use from any thread."""

    def __init__(self, engine):
        self._engine = engine
        self._req = None  # bound by ServingEngine.submit
        self._q = queue_mod.Queue()
        self._done = threading.Event()

    @property
    def rid(self):
        return self._req.rid

    @property
    def status(self):
        return self._req.status

    @property
    def preempts(self):
        return self._req.preempts

    @property
    def priority(self):
        """This request's priority class (serving/overload.py: smaller
        = more important; overload.NORMAL when the caller passed
        none)."""
        return self._req.priority

    @property
    def retry_after_s(self):
        """Back-off hint in seconds, set when this request was
        load-SHED (status ``SHED``) by the overload controller — the
        predicted time until the queue drains enough for a retry to
        stand a chance. None otherwise (including when the service-time
        model was not yet primed)."""
        return self._req.retry_after_s

    @property
    def trace_id(self):
        """This request's trace id (None when tracing is disabled or
        the trace was not sampled) — resolve it against the span ring
        (`profiler.tracing.export_trace`) or the `/traces/<id>`
        endpoint once the request is terminal."""
        return self._req.trace_id

    def tokens(self):
        """Tokens generated so far (stable snapshot)."""
        with self._engine._lock:
            return list(self._req.generated)

    def cost(self):
        """This request's :class:`~paddle_tpu.profiler.accounting.
        CostReport` — queue/prefill/decode/compile split of the device
        time attributed to it, token and prefix-coverage counts, and
        (once terminal) deadline_met. A detached snapshot, safe to keep;
        None when accounting is disarmed
        (``FLAGS_serving_accounting=0``)."""
        with self._engine._lock:
            c = self._req.cost
            return c.clone() if c is not None else None

    def cancel(self):
        self._engine.cancel(self)

    def stream(self, timeout=None):
        """Yield tokens as they are produced; ends when the request
        reaches a terminal status (check ``.status`` for CANCELLED /
        TIMEOUT / SHED — a shed request streamed nothing and carries
        ``retry_after_s``). If the ENGINE died the stream raises its fatal error
        instead of ending — truncated output must never look complete.
        ``timeout`` bounds the wait per token (queue.Empty past it)."""
        while True:
            item = self._q.get(timeout=timeout)
            if item is _SENTINEL:
                if self._req.status == RequestStatus.ERROR:
                    err = self._engine._error
                    if err is not None:
                        raise err
                return
            yield item

    def result(self, timeout=None):
        """Block until terminal; returns the generated tokens. Raises
        TimeoutError if the wait exceeds ``timeout``, or the engine's
        fatal error if serving itself died."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.rid} not finished within {timeout}s")
        if self._req.status == RequestStatus.ERROR:
            err = self._engine._error
            if err is not None:
                raise err
        return self.tokens()


class ServingEngine:
    """See module docstring. Construct once per model; context-manager
    friendly (``with ServingEngine(model) as eng: ...``)."""

    def __init__(self, model, *, max_batch=8, block_size=16,
                 max_seq_len=2048, num_blocks=None, temperature=0.0,
                 eos_token_id=None, dtype=None,
                 prefill_token_budget=None, max_queue=None,
                 bucket_cap=None, prefix_cache=None, accounting=None,
                 admission=None, brownout=None, kv_cache_dtype=None,
                 spec=None, spec_tokens=None, mesh=None,
                 background=True, ready=True, role=None,
                 paged_kernel=None):
        self._state = Lifecycle.WARMING
        # disaggregation role (serving/disagg.py): advertised through
        # the fleet registry and the stage-aware router; "mixed" is
        # byte-for-byte the pre-disagg engine
        self.role = "mixed" if role is None else str(role)
        if self.role not in ROLES:
            raise ValueError(
                f"ServingEngine: unknown role {role!r} "
                f"(expected one of {ROLES})")
        if self.role != "mixed" and \
                getattr(model, "tokens_per_block", 1) > 1:
            raise ValueError(
                f"ServingEngine: role {self.role!r} is disaggregated "
                "serving, which a block-diffusion model is not served "
                "with: its prefill samples no first token to hand off.")
        self._sched = Scheduler(
            model, max_batch=max_batch, block_size=block_size,
            max_seq_len=max_seq_len, num_blocks=num_blocks,
            temperature=temperature, eos_token_id=eos_token_id,
            dtype=dtype, prefill_token_budget=prefill_token_budget,
            max_queue=max_queue, bucket_cap=bucket_cap,
            prefix_cache=prefix_cache, accounting=accounting,
            admission=admission, brownout=brownout,
            kv_cache_dtype=kv_cache_dtype, spec=spec,
            spec_tokens=spec_tokens, mesh=mesh,
            paged_kernel=paged_kernel)
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._background = background
        self._thread = None
        self._closed = False
        self._error = None
        self._metrics_server = None
        self._registrar = None
        # fleet cache digest publication (serving/fleet_cache.py;
        # FLAGS_fleet_cache read here, the FLAGS_serving_prefix_cache
        # convention): disarmed = no publisher object, registry
        # payloads byte-for-byte pre-fleet-cache
        self._fleet_pub = None
        if bool(flags_mod.flag("FLAGS_fleet_cache")):
            from . import fleet_cache as _fleet_cache
            self._fleet_pub = _fleet_cache.DigestPublisher(self)
        # ready=False holds the engine in WARMING: submit() raises
        # NotReadyError until warmup() (or mark_ready()) flips READY;
        # routers see WARMING as not-routable on /readyz
        if ready:
            self._state = Lifecycle.READY
        _g_lifecycle_ready.set(1 if ready else 0)

    # -- submission ----------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens=32, *, deadline_s=None,
               deadline=None, priority=None, on_token=None,
               prefill_only=False):
        """Enqueue a request; returns a RequestHandle immediately.

        ``deadline_s`` (relative seconds) or ``deadline`` (a
        ``resilience.Deadline``) bounds total latency: expiry finishes
        the request with status TIMEOUT at the next step boundary and
        frees its blocks — and with the overload plane armed
        (``FLAGS_serving_admission``) a deadline the EWMA service-time
        model proves unmeetable raises ``AdmissionRejected`` HERE,
        with a ``retry_after_s``, instead of queueing doomed work.
        ``priority`` is an int class (serving/overload.py: smaller =
        more important, default ``overload.NORMAL``) — the shed order
        under pressure and the brownout ladder's admission floor.
        ``on_token(token)`` is called per generated token from the
        stepping thread — keep it fast.
        ``prefill_only`` (disaggregated serving, serving/disagg.py)
        runs ONLY the prefill stage: the request finishes ``DONE`` at
        its first token with the prompt's KV blocks registered for
        ``kv_transfer.export_prefix`` — requires the prefix cache.
        """
        handle = RequestHandle(self)

        def _sink_token(req, tok):
            handle._q.put(tok)
            if on_token is not None:
                on_token(tok)

        def _sink_finish(req):
            handle._q.put(_SENTINEL)
            handle._done.set()

        with self._cond:
            if self._closed:
                raise RuntimeError("ServingEngine is closed")
            if self._error is not None:
                raise RuntimeError(
                    "ServingEngine died; no new submissions") \
                    from self._error
            if self._state != Lifecycle.READY:
                # WARMING rejects like DRAINING: a request must never
                # silently pay the cold compiles warmup() owes
                # (/readyz and submit agree — test_router.py pins it)
                hint = "call warmup() first" \
                    if self._state == Lifecycle.WARMING \
                    else "route to another replica"
                raise NotReadyError(
                    f"ServingEngine is {self._state}; not accepting "
                    f"new requests ({hint})")
            if deadline is None and deadline_s is not None:
                deadline = resilience.Deadline.after(deadline_s)
            handle._req = self._sched.submit(
                prompt_ids, max_new_tokens, deadline=deadline,
                priority=priority, on_token=_sink_token,
                on_finish=_sink_finish, prefill_only=prefill_only)
            self._ensure_driver()
            self._cond.notify_all()
        return handle

    def submit_handoff(self, prompt_ids, first_token,
                       max_new_tokens=32, *, deadline_s=None,
                       deadline=None, priority=None, on_token=None,
                       trace_parent=None, transfer_us=0.0,
                       transfer_bytes=0, handoff_id=None):
        """Disaggregated decode-stage admission (serving/disagg.py):
        the prompt's KV blocks were imported into this engine's pool
        (``kv_transfer.import_prefix``) and ``first_token`` came from
        the prefill replica — possibly in ANOTHER process entirely
        (the rpc-served ``disagg._rpc_admit`` endpoint lands here) —
        admit straight into the batched decode step, zero prefill
        compute here. Same lifecycle gate as :meth:`submit`; the
        handle streams the FULL sequence (the first token re-emits
        through it). ``handoff_id`` (remote handoffs) is the
        pipeline-assigned cross-process identity, recorded on the
        admission span so the lease/relay records join the trace.
        Raises :class:`~.scheduler.HandoffError` when the imported
        prefix does not cover the prompt or no slot/blocks are free —
        the pipeline falls back to co-located serving."""
        handle = RequestHandle(self)

        def _sink_token(req, tok):
            handle._q.put(tok)
            if on_token is not None:
                on_token(tok)

        def _sink_finish(req):
            handle._q.put(_SENTINEL)
            handle._done.set()

        with self._cond:
            if self._closed:
                raise RuntimeError("ServingEngine is closed")
            if self._error is not None:
                raise RuntimeError(
                    "ServingEngine died; no new submissions") \
                    from self._error
            if self._state != Lifecycle.READY:
                hint = "call warmup() first" \
                    if self._state == Lifecycle.WARMING \
                    else "route to another replica"
                raise NotReadyError(
                    f"ServingEngine is {self._state}; not accepting "
                    f"new requests ({hint})")
            if deadline is None and deadline_s is not None:
                deadline = resilience.Deadline.after(deadline_s)
            handle._req = self._sched.admit_handoff(
                prompt_ids, first_token, max_new_tokens,
                deadline=deadline, priority=priority,
                on_token=_sink_token, on_finish=_sink_finish,
                trace_parent=trace_parent, transfer_us=transfer_us,
                transfer_bytes=transfer_bytes, handoff_id=handoff_id)
            self._ensure_driver()
            self._cond.notify_all()
        return handle

    def _ensure_driver(self):
        # caller holds the lock
        if self._background and self._thread is None:
            self._thread = threading.Thread(
                target=self._drive, name="paddle-tpu-serving",
                daemon=True)
            self._thread.start()

    def cancel(self, handle):
        with self._cond:
            self._sched.cancel(handle._req)
            self._cond.notify_all()

    # -- stepping ------------------------------------------------------

    @property
    def has_work(self):
        return self._sched.has_work

    @property
    def scheduler(self):
        return self._sched

    @property
    def cache(self):
        return self._sched.cache

    @property
    def accounting(self):
        """The engine's cost accountant (profiler/accounting.py): the
        null accountant when disarmed. ``engine.accounting.
        engine_report()`` / ``.goodput_line()`` aggregate goodput."""
        return self._sched.accounting

    @property
    def alerts(self):
        """The engine's AlertManager (None when accounting is
        disarmed); also served from the MetricsServer's /alerts."""
        return self._sched.alerts

    def step(self):
        """Run one scheduling iteration (foreground mode, or extra
        nudges in background mode)."""
        # a client thread holds the lock inside submit(): the engine's
        # thread waiting here is idle time that belongs to no phase of
        # the step
        with _tracing.phase("serving.engine.lock_wait"):
            self._lock.acquire()
        try:
            return self._sched.step()
        finally:
            self._lock.release()

    def run_until_idle(self):
        """Step until the scheduler is idle (foreground mode). Results
        arrive via the handles. Purely a stepping helper — admission
        stays open and the lifecycle does not move (contrast
        :meth:`drain`, the graceful shutdown)."""
        while True:
            with self._lock:
                if not self._sched.has_work:
                    return
            self.step()

    # -- lifecycle -----------------------------------------------------

    @property
    def lifecycle(self):
        """Current :class:`Lifecycle` state (served from /readyz)."""
        return self._state

    def warmup(self):
        """Precompile the bounded serving program set — every prefill
        bucket up to the cap (``bucket_lengths``: the log2(cap) ladder;
        a prompt longer than the cap compiles the program of its own
        length when it comes) plus the batched decode step — then flip
        WARMING -> READY. This is the cold-start gate: constructed
        with ``ready=False``, an engine rejects submits until warmup
        finishes, so live traffic NEVER pays a first-bucket compile.
        With the AOT cache armed (serving/aot_cache.py) each program
        loads from the on-disk store when warm (zero XLA compiles —
        tools/router_gate.py pins a warm second process) or compiles
        once and is stored for the next process.

        Runs the real jit entry points against throwaway slots (freed
        afterward; no requests exist in WARMING, so the pool is
        untouched by traffic). Idempotent — re-running in READY just
        revisits warm programs; raises past DRAINING like
        ``mark_ready``. Returns the number of programs visited."""
        with self._lock:
            if self._state in (Lifecycle.DRAINING, Lifecycle.CLOSED):
                raise RuntimeError(
                    f"cannot warmup a {self._state} engine")
            sched = self._sched
            cache = sched.cache
            buckets = bucket_lengths(cache.block_size, sched.bucket_cap,
                                     sched.max_seq_len)
            if sched.bucket_cap:
                # the ladder ends at the cap. Past it a prompt pads to
                # its own multiple of the block, one program a length
                # (192 of them at the flag's cap of 1024 under a
                # max_seq_len of 4096): rare by construction
                # (bucketing.py: cap at the p99 prompt), so each is
                # compiled when such a prompt first comes, not here
                buckets = [b for b in buckets
                           if b <= sched.bucket_cap] or buckets[:1]
            t0 = time.perf_counter_ns()
            n = 0
            kernel_mode = getattr(sched, "kernel_mode", None)
            width = getattr(sched.model, "tokens_per_block", 1)

            def decode_once(active):
                """The batched decode program: a token a slot, or a
                block-diffusion model's block step."""
                if width > 1:
                    def step(blocks):
                        return sched.model.paged_block_step(
                            cache, blocks, active,
                            kernel_mode=kernel_mode)[1]

                    zeros = np.zeros((cache.max_batch, width), np.int64)
                    host = sched.model.block_state(
                        zeros, zeros, zeros[:, 0], zeros[:, 0])
                else:
                    def step(toks):
                        return sched.model.paged_decode_step(
                            cache, toks, active,
                            temperature=sched.temperature,
                            kernel_mode=kernel_mode)

                    host = np.zeros((cache.max_batch,), np.int64)
                # as the loop calls it: with tokens (or open blocks) from
                # the host, with a step's own output still on the device,
                # and with that merged with a fresh slot's from the host
                step(merge_tokens(step(step(host)), host, active))

            # role-specialized warm sets (disaggregated serving):
            # prefill replicas run ONLY the bucket ladder (they never
            # decode), decode replicas warm ONLY the decode/spec
            # programs (handoffs never prefill here) — mixed warms both
            decoded = self.role == "prefill"
            if self.role == "decode":
                buckets = []
                slot = cache.alloc_slot(cache.block_size)
                if slot is not None:
                    try:
                        active = np.zeros((cache.max_batch,), bool)
                        active[slot] = True
                        decode_once(active)
                        n += 1
                        if sched.spec:
                            sk = sched.spec_tokens
                            sched.model.paged_spec_step(
                                cache,
                                np.zeros((cache.max_batch,), np.int64),
                                np.zeros((cache.max_batch, sk),
                                         np.int64),
                                np.full((cache.max_batch,), 1 + sk,
                                        np.int64), active)
                            n += 1
                    finally:
                        cache.free_slot(slot)
            with _tracing.span("serving.warmup", buckets=len(buckets)):
                for b in buckets:
                    slot = cache.alloc_slot(b)
                    if slot is None:
                        continue  # pool smaller than the ladder tail
                    try:
                        ids = np.zeros((b,), np.int64)
                        sched.model.paged_prefill(
                            cache, slot, ids,
                            temperature=sched.temperature, pad_to=b,
                            **sched.prefill_route)
                        n += 1
                        if not decoded:
                            # one decode step warms the (single) decode
                            # program; the next-position write past the
                            # allocated blocks lands in the null block,
                            # the bucketing convention
                            active = np.zeros((cache.max_batch,), bool)
                            active[slot] = True
                            decode_once(active)
                            decoded = True
                            n += 1
                            if sched.spec:
                                # the speculative verify sweep is one
                                # more static program — warm it too so
                                # the first live spec step never
                                # compiles (junk writes land past the
                                # slot or in the null block; the slot
                                # is freed below)
                                sk = sched.spec_tokens
                                sched.model.paged_spec_step(
                                    cache,
                                    np.zeros((cache.max_batch,),
                                             np.int64),
                                    np.zeros((cache.max_batch, sk),
                                             np.int64),
                                    np.full((cache.max_batch,), 1 + sk,
                                            np.int64), active)
                                n += 1
                    finally:
                        cache.free_slot(slot)
            _c_warmup_programs.inc(n)
            _h_warmup_us.observe((time.perf_counter_ns() - t0) / 1000.0)
        try:
            from ..distributed import watchdog
            watchdog.record_event("serving.warmup",
                                  meta={"programs": n}, status="lifecycle")
        except Exception:  # noqa: BLE001 — telemetry must not block boot
            pass
        if self._state == Lifecycle.WARMING:
            self.mark_ready()
        return n

    def mark_ready(self):
        """WARMING -> READY (no-op in READY; raises past that — a
        drained replica never becomes routable again)."""
        with self._cond:
            if self._state in (Lifecycle.DRAINING, Lifecycle.CLOSED):
                raise RuntimeError(
                    f"cannot mark_ready a {self._state} engine")
            self._state = Lifecycle.READY
            _g_lifecycle_ready.set(1)

    def drain(self, timeout=60):
        """Graceful shutdown of ADMISSION, not of the process: flips
        READY -> DRAINING (new ``submit()`` raises
        :class:`NotReadyError`; routers see /readyz go 503), lets
        every in-flight request finish naturally — terminal statuses
        unchanged, outputs bit-identical to an undrained run
        (tools/fleet_gate.py pins zero dropped requests) — then flips
        DRAINING -> CLOSED and deregisters from the fleet registry so
        routers drop the replica immediately. The metrics endpoint
        stays up for a final scrape; ``close()`` tears it down.
        Idempotent; ``timeout`` bounds the in-flight wait in
        background mode (TimeoutError past it, state stays DRAINING
        so a retry can finish the job). If the ENGINE dies mid-drain
        the drain is NOT graceful — the in-flight requests terminated
        ERROR, so the engine error re-raises here (state still flips
        CLOSED and the replica deregisters: a dead replica must leave
        the registry either way, but it never reports a clean
        ``serving.drain.completed``)."""
        with self._cond:
            if self._state == Lifecycle.CLOSED:
                return
            first = self._state != Lifecycle.DRAINING
            self._state = Lifecycle.DRAINING
            _g_lifecycle_ready.set(0)
            inflight = self._sched.inflight()
            span = _tracing.start_trace("serving.drain",
                                        inflight=inflight) \
                if first else _tracing.NULL
            if first:
                _c_drain_started.inc()
            self._cond.notify_all()
        if first:
            self._record_drain("started", inflight)
        # complete in-flight work: the background driver keeps
        # stepping (DRAINING is not CLOSED); foreground steps inline
        if self._thread is not None and self._thread.is_alive():
            deadline = None if timeout is None \
                else time.monotonic() + float(timeout)
            with self._cond:
                while self._sched.has_work and self._error is None:
                    if deadline is not None and \
                            time.monotonic() >= deadline:
                        span.end("timeout")
                        raise TimeoutError(
                            f"drain: {self._sched.inflight()} requests "
                            f"still in flight after {timeout}s")
                    self._cond.wait(0.02)
        else:
            with self._lock:
                while self._sched.has_work and self._error is None:
                    self._sched.step()
        with self._cond:
            was_closed = self._state == Lifecycle.CLOSED
            self._state = Lifecycle.CLOSED
            reg, self._registrar = self._registrar, None
            err = self._error
        if reg is not None:
            reg.deregister()
        if err is not None:
            # the driver died mid-drain: requests terminated ERROR,
            # not gracefully — never report a clean completion
            span.annotate(completed=False)
            span.end("error")
            raise RuntimeError(
                "drain: engine died before in-flight work could "
                "finish") from err
        if not was_closed:  # a concurrent drain lost the race: one edge
            _c_drain_completed.inc()
            self._record_drain("completed", 0)
        # the span belongs to the FIRST drainer, which may not be the
        # thread that won the CLOSED transition — end it regardless
        span.annotate(completed=True)
        span.end("CLOSED")

    @staticmethod
    def _record_drain(phase, inflight):
        """Flight-record the drain edges so post-mortems show deploys
        interleaved with the traffic around them."""
        try:
            from ..distributed import watchdog
            watchdog.record_event(f"serving.drain.{phase}",
                                  meta={"inflight": inflight},
                                  status="lifecycle")
        except Exception:  # noqa: BLE001 — telemetry must not block a drain
            pass

    def _drive(self):
        try:
            while True:
                with self._cond:
                    while not self._sched.has_work:
                        if self._closed:
                            return
                        # nothing to run: idle that is nobody's fault
                        with _tracing.phase("serving.engine.no_work"):
                            self._cond.wait()
                    if self._closed and not self._sched.has_work:
                        return
                self.step()
        except BaseException as e:  # noqa: BLE001 — fail loud, not silent
            with self._cond:
                self._error = e
                self._sched.fail_all(e)
            resilience.degrade("serving.engine", exc=e)

    # -- telemetry export ----------------------------------------------

    def serve_metrics(self, port=0, host="127.0.0.1", store=None,
                      replica_id=None):
        """Attach a scrapeable telemetry endpoint to this engine
        (idempotent; closed with the engine). Routes: ``/metrics``
        (OpenMetrics text), ``/metrics/delta`` (per-second rates),
        ``/healthz`` (SLO gauges + engine liveness — 503 once the
        driver died or the engine closed), ``/readyz`` (the drain
        lifecycle — 503 unless READY), ``/alerts`` (SLO burn-rate
        incidents from this engine's AlertManager), ``/traces`` and
        ``/traces/<id>`` (Chrome/Perfetto span exports). ``port=0``
        (the default) binds an ephemeral port — ALWAYS read the bound
        one from ``.port``/``.url()`` on the returned server instead of
        hardcoding (multi-replica routers discover replicas this way).

        ``store`` (a ``distributed.store.TCPStore`` client) opts this
        replica into the FLEET REGISTRY (profiler/fleet.py): the scrape
        address + identity self-register under a TTL'd heartbeat, so a
        FleetAggregator discovers, scrapes, and health-scores it;
        ``drain()``/``close()`` deregister. With ``FLAGS_fleet=0`` or
        no store this is a byte-for-byte no-op (no thread, fleet.*
        counters silent)."""
        with self._lock:
            if self._metrics_server is None:
                from ..profiler.export import MetricsServer
                self._metrics_server = MetricsServer(
                    port=port, host=host, health_extra=self._health_view,
                    alerts=self._sched.alerts, ready=self._ready_view)
            srv = self._metrics_server
            register = store is not None and self._registrar is None \
                and self._state not in (Lifecycle.DRAINING,
                                        Lifecycle.CLOSED)
        if register:
            from ..profiler import fleet as _fleet
            if _fleet.armed(store):
                reg = _fleet.Registrar(
                    store, srv.url(""), replica_id=replica_id,
                    status_fn=lambda: self._state, role=self.role)
                # pool geometry rides every payload UNCONDITIONALLY
                # (serving/fleet_cache.geometry_payload): peers refuse
                # a frame-exchange mismatch BEFORE anything ships
                from . import fleet_cache as _fleet_cache
                reg.add_extra(
                    lambda: _fleet_cache.geometry_payload(self))
                if self._fleet_pub is not None:
                    # the digest advertisement (FLAGS_fleet_cache,
                    # read at construction) joins the same beat
                    reg.add_extra(self._fleet_pub.payload)
                reg.start()
                with self._lock:
                    if self._registrar is None:
                        self._registrar = reg
                    else:  # lost an unlikely double-attach race
                        reg.deregister()
        return srv

    def _health_view(self):
        with self._lock:
            alive = self._error is None and not self._closed
            view = {"engine": {
                "closed": self._closed,
                "lifecycle": self._state,
                "queue": len(self._sched.queue),
                "running": len(self._sched.running)}}
            if self._error is not None:
                view["engine"]["error"] = \
                    f"{type(self._error).__name__}: {self._error}"
        if not alive:
            view["status"] = "draining" if self._error is None \
                else "dead"
        return view

    def _ready_view(self):
        """/readyz body: routability, distinct from /healthz liveness —
        a DRAINING replica is alive (scrape it!) but must receive no
        new traffic."""
        with self._lock:
            state = self._state
            body = {"ready": state == Lifecycle.READY
                    and self._error is None,
                    "state": state, "attached": True,
                    "inflight": self._sched.inflight()}
            if self._error is not None:
                body["error"] = \
                    f"{type(self._error).__name__}: {self._error}"
        return body

    # -- lifecycle -----------------------------------------------------

    def close(self, cancel_pending=True, timeout=60):
        """Stop serving. ``cancel_pending=True`` (default) cancels all
        live requests (they finish CANCELLED at the final sweep);
        ``False`` drains them first."""
        with self._cond:
            self._closed = True
            self._state = Lifecycle.CLOSED
            _g_lifecycle_ready.set(0)
            reg, self._registrar = self._registrar, None
            if cancel_pending:
                for req in list(self._sched.queue):
                    req.cancel_requested = True
                for req in list(self._sched.running.values()):
                    req.cancel_requested = True
            self._cond.notify_all()
        if reg is not None:
            reg.deregister()  # routers drop us before the join below
        if self._thread is not None:
            self._thread.join(timeout)
        # foreground mode (or a dead driver): flush remaining work so
        # every handle reaches a terminal status
        with self._lock:
            if self._error is None:
                while self._sched.has_work:
                    self._sched.step()
            server, self._metrics_server = self._metrics_server, None
        if server is not None:
            server.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
