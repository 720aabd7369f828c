"""Per-request cost attribution, engine goodput, and capacity accounting.

Iteration-level batching bills ONE device step to many concurrent
requests (the decode dispatch is a single jitted program for every live
slot), so "how much did this request cost" is not a measurement — it is
an *attribution policy*. This module implements the policy the serving
scheduler applies every step:

- **Token-proportional apportionment.** Each step's measured wall time
  is split across the requests that did work that step, in proportion
  to the tokens they prefilled/decoded. A prefill of 64 computed tokens
  weighs 64; a decode weighs 1. The split is exact by construction:
  per-step attributed shares + directly-billed compile time + the idle
  remainder of empty steps always sum to the measured step time
  (``tools/accounting_gate.py`` and tests pin this closure property).
- **Compile billed to the trigger.** XLA compile seconds observed
  during a request's prefill (a fresh bucket) bill to THAT request's
  ``compile_us``, not the batch — the first request of a bucket pays
  for warming it. Decode-program compiles split across that step's
  decode participants.
- **Re-prefill billed to the preemption.** A preempted victim's
  re-prefill work lands in ``reprefill_us`` (and the engine-level
  ``accounting.reprefill_us`` waste counter), not ``prefill_us`` — the
  cost of the preemption event stays visible instead of inflating the
  request's apparent prefill price.
- **Prefix hits billed at extend-only cost.** A cache-hitting request's
  prefill note carries only its computed (uncovered, bucketed) tokens,
  so covered tokens are free in the apportionment — exactly mirroring
  the zero-FLOPs-for-covered-blocks contract of the prefix cache.

Each request accumulates a :class:`CostReport` (exposed as
``RequestHandle.cost()``); the engine aggregates **goodput** —
deadline-met tokens per measured device-second of engine stepping
(attributed + compile + idle) — plus tokens/s and an
MFU estimate from model-config FLOPs. Capacity accounting folds the KV
pool occupancy breakdown (active/shared/cached-free/free) and live-array
HBM sampling into gauges and the "Capacity View" / "Goodput" sections of
``profiler.summary()``.

Disarmed (``FLAGS_serving_accounting=0``, read at Scheduler
construction) the scheduler holds the preallocated :data:`NULL`
accountant whose methods are no-ops — the per-step overhead is a few
attribute lookups (``tools/accounting_gate.py`` pins the budget, the
``testing/faults.py``/tracing school of nearly-free-when-off).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from . import metrics as _metrics

__all__ = ["CostReport", "Accountant", "NULL", "flops_per_token",
           "matmul_params", "detect_peak_flops", "peak_bf16_flops",
           "PEAK_BF16_FLOPS"]

# engine-level aggregates (registry: rendered by the summary "Goodput"
# section, scraped from /metrics; multiple engines sum into one family)
_c_steps = _metrics.counter("accounting.steps")
_c_device_us = _metrics.counter("accounting.device_us")
_c_attributed_us = _metrics.counter("accounting.attributed_us")
_c_compile_us = _metrics.counter("accounting.compile_us")
_c_reprefill_us = _metrics.counter("accounting.reprefill_us")
_c_idle_us = _metrics.counter("accounting.idle_us")
_c_tokens = _metrics.counter("accounting.tokens_emitted")
_c_processed = _metrics.counter("accounting.tokens_processed")
_c_goodput = _metrics.counter("accounting.goodput_tokens")
_c_missed = _metrics.counter("accounting.deadline_missed_tokens")
# compile seconds the AOT cache saved (serving/aot_cache.py): an
# INFORMATIONAL axis beside the closure — saved time never happened,
# so it is not part of attributed + compile + idle == step_us
_c_aot_saved = _metrics.counter("accounting.aot_saved_us")
_g_mfu = _metrics.gauge("accounting.mfu")
_g_active = _metrics.gauge("serving.kv.active_blocks")
_g_free = _metrics.gauge("serving.kv.free_blocks")
_g_pool_bytes = _metrics.gauge("serving.kv.pool_bytes")
_g_live_bytes = _metrics.gauge("memory.live_bytes")
_g_live_arrays = _metrics.gauge("memory.live_arrays")


class CostReport:
    """One request's accumulated cost attribution. All time fields are
    microseconds of *attributed device-step wall time* (they sum across
    concurrent requests to the engine's measured step time — see module
    docstring), except ``queue_us``/``ttft_us`` which are this
    request's own wall-clock latencies."""

    __slots__ = ("rid", "status", "queue_us", "prefill_us",
                 "reprefill_us", "decode_us", "compile_us",
                 "aot_saved_us", "ttft_us", "transfer_us",
                 "transfer_bytes", "relay_us",
                 "tokens_prefilled", "tokens_decoded", "tokens_emitted",
                 "covered_tokens", "spec_proposed", "spec_accepted",
                 "preempts", "steps", "deadline_met")

    def __init__(self, rid):
        self.rid = rid
        self.status = None          # terminal RequestStatus, set at finish
        self.queue_us = 0.0
        self.prefill_us = 0.0       # attributed first-prefill share
        self.reprefill_us = 0.0     # attributed preemption re-prefill share
        self.decode_us = 0.0        # attributed decode-step shares
        self.compile_us = 0.0       # XLA compiles this request triggered
        self.aot_saved_us = 0.0     # compile time an AOT-cache hit avoided
        #                             (informational: NOT in attributed_us —
        #                             saved time was never on the device)
        self.ttft_us = None
        self.transfer_us = 0.0      # disaggregated KV handoff wall time
        #                             (informational, like aot_saved_us:
        #                             fabric time, not device-step time)
        self.transfer_bytes = 0     # KV bytes moved for the handoff
        self.relay_us = 0.0         # cross-process token-relay serve time
        #                             (remote handoffs, serving/disagg.py:
        #                             decode-side pull handling — another
        #                             informational fabric axis, NEVER in
        #                             attributed_us; transfer_us semantics
        #                             are unchanged by it)
        self.tokens_prefilled = 0   # computed (padded) prefill tokens
        self.tokens_decoded = 0     # batched decode steps participated in
        self.tokens_emitted = 0     # tokens streamed (prefill + decode)
        self.covered_tokens = 0     # prefix-cache tokens served for free
        self.spec_proposed = 0      # draft tokens verified for this request
        self.spec_accepted = 0      # ...of which greedy decode accepted
        self.preempts = 0
        self.steps = 0              # scheduler steps this request was billed
        self.deadline_met = None    # None: no deadline; else bool

    @property
    def attributed_us(self):
        """Total device time billed to this request."""
        return (self.prefill_us + self.reprefill_us + self.decode_us
                + self.compile_us)

    def as_dict(self):
        return {k: getattr(self, k) for k in self.__slots__} | {
            "attributed_us": self.attributed_us}

    def clone(self):
        c = CostReport(self.rid)
        for k in self.__slots__:
            setattr(c, k, getattr(self, k))
        return c

    def summary(self):
        """One human line: the per-request bill."""
        dl = "" if self.deadline_met is None else \
            f" deadline_met={self.deadline_met}"
        ttft = f"{self.ttft_us / 1000.0:.1f}ms" \
            if self.ttft_us is not None else "n/a"
        return (f"rid={self.rid} status={self.status} "
                f"queue={self.queue_us / 1000.0:.1f}ms ttft={ttft} | "
                f"attributed={self.attributed_us / 1000.0:.2f}ms "
                f"(prefill={self.prefill_us / 1000.0:.2f} "
                f"decode={self.decode_us / 1000.0:.2f} "
                f"compile={self.compile_us / 1000.0:.2f} "
                f"reprefill={self.reprefill_us / 1000.0:.2f}) | "
                f"tokens={self.tokens_emitted} "
                f"prefilled={self.tokens_prefilled} "
                f"covered={self.covered_tokens} "
                + (f"spec={self.spec_accepted}/{self.spec_proposed} "
                   if self.spec_proposed else "")
                + f"preempts={self.preempts}{dl}")

    def __repr__(self):
        return f"CostReport({self.summary()})"


# -- model FLOPs / MFU ------------------------------------------------------

def matmul_params(config):
    """Matmul-participating parameter count from a transformer config
    (attention projections + MLP + LM head; norms/embeddings excluded
    as they do no per-token matmul FLOPs). Works for any config with
    the Llama/GPT field names; returns None if fields are missing."""
    try:
        h = config.hidden_size
        head_dim = h // config.num_heads
        per_layer = (2 * h * config.num_heads * head_dim          # q, o
                     + 2 * h * config.num_kv_heads * head_dim     # k, v
                     + 3 * h * config.intermediate_size)          # mlp
        return (config.num_layers * per_layer
                + config.vocab_size * h)                          # lm head
    except AttributeError:
        return None


def flops_per_token(config):
    """Forward FLOPs per generated token: 2 x matmul params (the
    standard lower-bound estimate; attention-score FLOPs grow with
    context and are excluded, so the MFU derived from this is slightly
    optimistic at long context). None when the config is unknown."""
    p = matmul_params(config)
    return None if p is None else 2.0 * p


# bf16 peak FLOP/s per chip, keyed by the exact ``device_kind`` jax
# reports (names as in jax._src.test_util.is_device_tpu: v5e is "TPU v5
# lite", v5p is "TPU v5", v6e is "TPU v6 lite"; peaks from the Google
# Cloud TPU documentation of each generation). The one peak table of the
# repo: bench.py reads it too.
PEAK_BF16_FLOPS = {
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
}


def peak_bf16_flops(device_kind):
    """Peak bf16 FLOP/s of a ``device_kind``. A device that is not in the
    table is an error, never a default: a utilization over a guessed
    peak is a wrong number under a device metric's name."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak known for device_kind {device_kind!r}; add it "
            f"to profiler.accounting.PEAK_BF16_FLOPS with its source"
        ) from None


def detect_peak_flops():
    """Peak device FLOPs for the MFU estimate: the
    ``ACCOUNTING_PEAK_FLOPS`` env override, else the table entry of the
    default device; None (MFU unreported) on CPU. An accelerator that
    is not in the table raises."""
    env = os.environ.get("ACCOUNTING_PEAK_FLOPS")
    if env:
        return float(env)
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    return peak_bf16_flops(dev.device_kind)


class _Note:
    """One unit of per-step work awaiting apportionment."""

    __slots__ = ("req", "kind", "tokens", "compile_us", "aot_saved_us",
                 "emitted")

    def __init__(self, req, kind, tokens, compile_us=0.0,
                 aot_saved_us=0.0, emitted=1):
        self.req = req
        self.kind = kind          # "prefill" | "reprefill" | "decode"
        self.tokens = tokens      # apportionment weight (computed positions)
        self.compile_us = compile_us
        self.aot_saved_us = aot_saved_us
        self.emitted = emitted    # tokens streamed to the caller


# how often (seconds) update_capacity re-scans jax.live_arrays() — the
# scan is O(live arrays), so it is time-throttled, not per-step
_HBM_SAMPLE_S = 2.0


class Accountant:
    """Per-engine cost attribution state machine. The scheduler drives
    it: ``step_begin`` -> ``note_*`` during the step -> ``step_end``
    (apportionment) and ``on_finish`` at each terminal status. NOT
    thread-safe by itself — the scheduler's caller serializes steps
    (serving.frontend holds the engine lock)."""

    armed = True

    def __init__(self, config=None, peak_flops=None, step_log_cap=2048):
        self.flops_per_token = flops_per_token(config) \
            if config is not None else None
        self.peak_flops = peak_flops if peak_flops is not None \
            else detect_peak_flops()
        # engine-local totals (registry counters aggregate engines)
        self.device_us = 0.0
        self.attributed_us = 0.0
        self.compile_us = 0.0
        self.aot_saved_us = 0.0
        self.reprefill_us = 0.0
        self.idle_us = 0.0
        self.tokens_emitted = 0    # tokens streamed to callers
        self.tokens_processed = 0  # computed (padded) prefill + decode
        self.goodput_tokens = 0
        self.missed_tokens = 0
        self.requests_finished = 0
        # per-step closure log (tests + the accounting gate read it)
        self.step_log = deque(maxlen=step_log_cap)
        self._notes = []
        self._decode_compile_us = 0.0
        self._decode_aot_saved_us = 0.0
        self._last_hbm_sample = 0.0
        self._lock = threading.Lock()  # guards engine_report vs step_end

    # -- scheduler hooks (one step = begin .. notes .. end) ---------------

    def attach(self, req):
        """Bind a fresh CostReport at submit time."""
        req.cost = CostReport(req.rid)

    def step_begin(self):
        self._notes = []
        self._decode_compile_us = 0.0
        self._decode_aot_saved_us = 0.0

    def note_queue_wait(self, req, wait_us):
        if req.cost is not None:
            req.cost.queue_us = float(wait_us)

    def note_prefill(self, req, computed_tokens, covered, compile_us,
                     reprefill, aot_saved_us=0.0, emitted=1):
        """A prefill ran for ``req`` this step: ``computed_tokens`` is
        the padded tail it actually computed (covered prefix tokens are
        NOT in it — they are free), ``compile_us`` any XLA compile its
        dispatch triggered (billed direct to this request), and
        ``aot_saved_us`` any compile time an AOT-cache hit AVOIDED
        (credited to this request, kept outside the closure sum —
        saved time never ran on the device). ``emitted``: the token it
        sampled (a block-diffusion prefill samples none)."""
        kind = "reprefill" if reprefill else "prefill"
        self._notes.append(_Note(req, kind, max(int(computed_tokens), 1),
                                 float(compile_us),
                                 float(aot_saved_us)))
        c = req.cost
        if c is not None:
            c.tokens_prefilled += int(computed_tokens)
            c.covered_tokens += int(covered)
            c.tokens_emitted += int(emitted)

    def note_decode(self, req):
        """``req`` received one token from this step's batched decode."""
        self._notes.append(_Note(req, "decode", 1))
        c = req.cost
        if c is not None:
            c.tokens_decoded += 1
            c.tokens_emitted += 1

    def note_block(self, req, positions, emitted):
        """``req`` took part in this step's block forward
        (block-diffusion decoding, scheduler ``_emit_blocks``): the
        device computed ``positions`` for it, a denoising forward's as a
        commit's (the apportionment weight), and ``emitted`` tokens
        streamed to the caller: none unless the forward committed the
        block."""
        self._notes.append(_Note(req, "decode", int(positions),
                                 emitted=int(emitted)))
        c = req.cost
        if c is not None:
            c.tokens_decoded += int(emitted)
            c.tokens_emitted += int(emitted)

    def note_spec(self, req, emitted, proposed, accepted):
        """``req`` participated in this step's speculative verify sweep
        (scheduler ``_decode_spec``): the device computed ``1 +
        proposed`` positions for it — THE apportionment weight, so
        wasted (rejected) draft positions bill real device time to the
        request that speculated them — and ``emitted`` tokens (1 +
        accepted drafts, eos-truncated) streamed to the caller. A
        spec step with zero proposals never reaches here (the
        scheduler falls back to the plain decode note)."""
        self._notes.append(_Note(req, "decode", 1 + int(proposed),
                                 emitted=int(emitted)))
        c = req.cost
        if c is not None:
            c.tokens_decoded += int(emitted)
            c.tokens_emitted += int(emitted)
            c.spec_proposed += int(proposed)
            c.spec_accepted += int(accepted)

    def note_transfer(self, req, transfer_us, transfer_bytes):
        """``req`` arrived via a disaggregated KV handoff
        (``Scheduler.admit_handoff``): bill the fabric time and bytes
        to its cost report. Informational like ``aot_saved_us`` — the
        transfer ran on the wire, not the device, so it stays outside
        the step-closure sum; the decode replica carries it because
        that is where the handed-off request lands."""
        c = req.cost
        if c is not None:
            c.transfer_us += float(transfer_us)
            c.transfer_bytes += int(transfer_bytes)

    def note_relay(self, req, relay_us):
        """``req`` is being served to a REMOTE caller over the token
        relay (disagg ``_rpc_pull``): bill this pull's decode-side
        handling time. Informational like ``transfer_us`` — wire
        bookkeeping, not device time, outside the step-closure sum."""
        c = req.cost
        if c is not None:
            c.relay_us += float(relay_us)

    def note_decode_compile(self, compile_us):
        """XLA compile observed around the batched decode dispatch
        (engine warmup): split across this step's decode participants."""
        if compile_us > 0.0:
            self._decode_compile_us += float(compile_us)

    def note_decode_aot_saved(self, saved_us):
        """Compile time an AOT-cache hit avoided around the batched
        decode dispatch: split across this step's decode participants,
        like :meth:`note_decode_compile` (informational axis)."""
        if saved_us > 0.0:
            self._decode_aot_saved_us += float(saved_us)

    def step_end(self, step_us):
        """Apportion the measured step wall time: direct compile bills
        first (clamped to the step), the remainder splits across notes
        in proportion to tokens. The closure invariant — attributed +
        compile + idle == step_us exactly (modulo float) — holds by
        construction and is what the tests/gate pin."""
        step_us = float(step_us)
        notes = self._notes
        dec_notes = sum(1 for n in notes if n.kind == "decode")
        if dec_notes and self._decode_compile_us > 0.0:
            share = self._decode_compile_us / dec_notes
            for n in notes:
                if n.kind == "decode":
                    n.compile_us += share
        if dec_notes and self._decode_aot_saved_us > 0.0:
            share = self._decode_aot_saved_us / dec_notes
            for n in notes:
                if n.kind == "decode":
                    n.aot_saved_us += share
        if not dec_notes and self._decode_compile_us > 0.0:
            # no decode participants (can't happen today): keep closure
            # by treating it as part of the idle remainder
            pass
        total_compile = sum(n.compile_us for n in notes)
        total_saved = sum(n.aot_saved_us for n in notes)
        scale = 1.0
        if total_compile > step_us:
            # jax's compile clock can disagree with our step clock at
            # the edge; scale bills down so attribution never exceeds
            # the measured step (scale 0 when the step clock floored)
            scale = step_us / total_compile
        direct = min(total_compile * scale, step_us)
        remainder = step_us - direct
        total_tokens = sum(n.tokens for n in notes)
        attributed = 0.0
        reprefill = 0.0
        stepped = set()  # a request billed twice this step (prefill +
        #                  decode) still participated in ONE step
        for n in notes:
            share = remainder * (n.tokens / total_tokens) \
                if total_tokens else 0.0
            bill = n.compile_us * scale
            c = n.req.cost
            if c is not None:
                if n.kind == "prefill":
                    c.prefill_us += share
                elif n.kind == "reprefill":
                    c.reprefill_us += share
                else:
                    c.decode_us += share
                c.compile_us += bill
                # savings bill UNSCALED: they are not wall time of this
                # step, so the closure clamp never applies to them
                c.aot_saved_us += n.aot_saved_us
                if id(c) not in stepped:
                    stepped.add(id(c))
                    c.steps += 1
            attributed += share
            if n.kind == "reprefill":
                reprefill += share
        idle = step_us - attributed - direct if not notes else 0.0
        # emitted counts tokens STREAMED to callers (a speculative
        # decode note streams 1 + accepted per request); the token-
        # proportional weights (padded prefill tails, computed spec
        # positions) are a different axis, tracked as "processed"
        emitted = sum(n.emitted for n in notes)
        with self._lock:
            self.device_us += step_us
            self.attributed_us += attributed
            self.compile_us += direct
            self.aot_saved_us += total_saved
            self.reprefill_us += reprefill
            self.idle_us += idle
            self.tokens_emitted += emitted
            self.tokens_processed += total_tokens
        self.step_log.append({"step_us": step_us,
                              "attributed_us": attributed,
                              "compile_us": direct, "idle_us": idle,
                              "aot_saved_us": total_saved,
                              "notes": len(notes)})
        _c_steps.inc()
        _c_device_us.inc(step_us)
        _c_attributed_us.inc(attributed)
        _c_compile_us.inc(direct)
        if total_saved:
            _c_aot_saved.inc(total_saved)
        _c_reprefill_us.inc(reprefill)
        _c_idle_us.inc(idle)
        if notes:
            _c_tokens.inc(emitted)
            _c_processed.inc(total_tokens)
        self._notes = []
        self._decode_compile_us = 0.0
        self._decode_aot_saved_us = 0.0

    def on_finish(self, req, status):
        """Finalize the request's report at its terminal status and
        fold it into goodput: deadline-met tokens count toward the
        numerator (no deadline + DONE counts as met)."""
        c = req.cost
        if c is None:
            return
        c.status = status
        c.preempts = req.preempts
        if req.first_token_at is not None:
            c.ttft_us = (req.first_token_at - req.submitted_at) * 1e6
        tokens = len(req.generated)
        met = None
        if status == "DONE":
            met = True if req.deadline is None \
                else not req.deadline.expired()
        elif req.deadline is not None and req.deadline.expired():
            # a cancel/error BEFORE the deadline passed is not a miss —
            # the outcome stays None (undefined), like deadline-less
            met = False
        c.deadline_met = met
        with self._lock:
            self.requests_finished += 1
            if status == "DONE" and met is not False:
                self.goodput_tokens += tokens
                _c_goodput.inc(tokens)
            elif met is False:
                # only genuine deadline outcomes land here — tokens of
                # deadline-LESS cancels/errors are simply not goodput,
                # they are not "missed deadlines"
                self.missed_tokens += tokens
                _c_missed.inc(tokens)

    # -- capacity accounting ----------------------------------------------

    def update_capacity(self, cache):
        """Refresh the KV-occupancy gauges from the pool's host
        metadata (cheap, every step) and — time-throttled — sample
        live-array HBM. Also keeps the MFU gauge live (a scraped
        engine must not need someone to call engine_report() first).
        Returns the occupancy dict."""
        occ = cache.occupancy()
        _g_active.set(occ["active"])
        _g_free.set(occ["free"])
        _g_pool_bytes.set(cache.pool_bytes())
        if self.flops_per_token and self.peak_flops and self.device_us:
            _g_mfu.set(round(
                (self.tokens_processed / (self.device_us / 1e6))
                * self.flops_per_token / self.peak_flops, 6))
        now = time.monotonic()
        if now - self._last_hbm_sample >= _HBM_SAMPLE_S:
            self._last_hbm_sample = now
            self._sample_hbm()
        return occ

    @staticmethod
    def _sample_hbm():
        try:
            import jax
            arrs = [a for a in jax.live_arrays()
                    if getattr(a, "is_deleted", lambda: False)() is False]
            _g_live_arrays.set(len(arrs))
            _g_live_bytes.set(sum(int(getattr(a, "nbytes", 0))
                                  for a in arrs))
        except Exception:  # noqa: BLE001 — sampling must never break a step
            pass

    # -- aggregates -------------------------------------------------------

    def engine_report(self):
        """Engine-level goodput: deadline-met tokens per MEASURED
        device-second (the denominator includes direct compile and
        idle steps — they are real engine cost), raw tokens/s, and the
        model-FLOPs MFU estimate (None without a known peak). Safe to
        call from any thread."""
        with self._lock:
            device_s = self.device_us / 1e6
            tokens = self.tokens_emitted
            goodput_tokens = self.goodput_tokens
            rep = {"device_s": device_s,
                   "tokens": tokens,
                   "tokens_processed": self.tokens_processed,
                   "goodput_tokens": goodput_tokens,
                   "missed_tokens": self.missed_tokens,
                   "requests_finished": self.requests_finished,
                   "attributed_us": self.attributed_us,
                   "compile_us": self.compile_us,
                   "aot_saved_us": self.aot_saved_us,
                   "reprefill_us": self.reprefill_us,
                   "idle_us": self.idle_us}
        tps = tokens / device_s if device_s > 0 else 0.0
        rep["tokens_per_device_s"] = tps
        rep["goodput_tokens_per_device_s"] = \
            goodput_tokens / device_s if device_s > 0 else 0.0
        mfu = None
        if self.flops_per_token and self.peak_flops and device_s > 0:
            # MFU measures COMPUTE utilization, so it runs on the
            # processed-token axis (padded prefill tails included) —
            # emitted tokens/s would undercount prefill FLOPs entirely
            mfu = (rep["tokens_processed"] / device_s) \
                * self.flops_per_token / self.peak_flops
            _g_mfu.set(round(mfu, 6))
        rep["mfu"] = mfu
        return rep

    def goodput_line(self):
        """The one-line engine summary (examples print it at exit)."""
        r = self.engine_report()
        mfu = f"{r['mfu']:.3f}" if r["mfu"] is not None else "n/a"
        return (f"goodput: {r['goodput_tokens_per_device_s']:.1f} "
                f"deadline-met tok/s over {r['device_s']:.2f} device-s "
                f"({r['tokens_per_device_s']:.1f} tok/s raw, "
                f"mfu~{mfu}; compile {r['compile_us'] / 1000:.1f}ms, "
                f"reprefill waste {r['reprefill_us'] / 1000:.1f}ms, "
                f"idle {r['idle_us'] / 1000:.1f}ms)")


class _NullAccountant(Accountant):
    """Disarmed accounting: every scheduler hook is a no-op (the
    nearly-free-when-off contract, pinned by tools/accounting_gate.py).
    ``req.cost`` stays None, so ``RequestHandle.cost()`` returns None."""

    armed = False

    def __init__(self):  # no registry traffic, no config math
        pass

    def attach(self, req):
        pass

    def step_begin(self):
        pass

    def note_queue_wait(self, req, wait_us):
        pass

    def note_prefill(self, req, computed_tokens, covered, compile_us,
                     reprefill, aot_saved_us=0.0, emitted=1):
        pass

    def note_decode(self, req):
        pass

    def note_block(self, req, positions, emitted):
        pass

    def note_spec(self, req, emitted, proposed, accepted):
        pass

    def note_transfer(self, req, transfer_us, transfer_bytes):
        pass

    def note_relay(self, req, relay_us):
        pass

    def note_decode_compile(self, compile_us):
        pass

    def note_decode_aot_saved(self, saved_us):
        pass

    def step_end(self, step_us):
        pass

    def on_finish(self, req, status):
        pass

    def update_capacity(self, cache):
        pass

    def engine_report(self):
        return None

    def goodput_line(self):
        return "goodput: accounting disarmed (FLAGS_serving_accounting=0)"


NULL = _NullAccountant()
