"""Overload control plane: deadline-aware admission, priority load
shedding, and a brownout ladder over the serving scheduler.

Before this module the serving stack's only defense against overload
was the bounded FCFS queue (``QueueFullError`` at
``FLAGS_serving_max_queue``): a request whose deadline was provably
unmeetable still queued, paid its prefill, and only then hit TIMEOUT at
a step boundary — wasted device time exactly when the engine could
least afford it. This module turns the signals the observability PRs
built (per-token prefill/decode costs from the accounting axes, KV
occupancy from ``PagedKVCache.occupancy()``, queue depth) into the
load-shedding control loop a production front door needs:

- **Deadline-aware admission** (``FLAGS_serving_admission``). A
  :class:`ServiceTimeModel` keeps EWMAs of the per-token prefill cost
  and per-step decode cost — the same measured quantities
  ``profiler/accounting.py`` apportions, observed compile-free at each
  dispatch. At ``submit()`` it predicts queue-wait + TTFT; a request
  whose ``deadline_s`` cannot be met even at
  ``FLAGS_admission_optimism`` times the prediction (0.5: even HALF
  the predicted TTFT busts the deadline) is rejected immediately with
  :class:`AdmissionRejected` carrying a ``retry_after_s`` estimate —
  fail fast, never pay prefill for a corpse. The model only rejects
  once primed (a handful of observed prefills), so a cold engine
  admits everything.

- **Priority load shedding** (same flag). ``submit(priority=)`` takes
  an int class — smaller is more important (:data:`HIGH` = 0,
  :data:`NORMAL` = 1 the default, :data:`LOW` = 2; any int works).
  Each step the controller computes an overload **pressure** (max of
  queue depth beyond the free decode slots vs
  ``FLAGS_shed_queue_frac``·max_queue, KV occupancy vs
  ``FLAGS_shed_kv_frac``, predicted queue wait vs ``FLAGS_shed_wait_s``
  — all zero below the ``FLAGS_shed_min_queue`` backlog floor: a full
  pool with an empty queue is a busy engine keeping up, not overload).
  At pressure >= 1.0 the scheduler sheds **lowest-priority, newest
  queued** requests (the top class is never watermark-shed) to the
  terminal status ``SHED`` — blocks never allocated, handle carries
  ``retry_after_s`` — until pressure drops or only the top class
  remains. Preemption victim choice becomes priority-then-newest.

- **Brownout ladder** (``FLAGS_serving_brownout``). An edge-triggered,
  hysteresis-guarded controller (the ``profiler/alerts.py`` school)
  walks ordered stages under SUSTAINED pressure — stage 1 clamps
  effective ``max_new_tokens`` to ``FLAGS_brownout_clamp_tokens``,
  stage 2 rejects below-NORMAL submits, stage 3 admits only the top
  class — entering after ``FLAGS_brownout_enter_steps`` consecutive
  over-pressure steps and exiting (deliberately slower) after
  ``FLAGS_brownout_exit_steps`` steps at or below
  ``FLAGS_brownout_exit_pressure``. The current rung is the
  ``serving.brownout.stage`` gauge; every transition is counted and
  flight-recorded.

Both flags are read at Scheduler construction (the
``FLAGS_serving_accounting`` convention); with both off the scheduler
holds the preallocated :data:`NULL` controller — every hook a no-op,
behavior byte-for-byte pre-overload, ``serving.shed`` /
``serving.admission.*`` / ``serving.brownout.*`` counters silent
(``tools/overload_gate.py`` pins the revert). Survivors of a shedding
run stay greedy bit-identical to an uncontended run: shedding only
ever removes QUEUED requests (no slot, no blocks), so the PR 5/8
preemption pin extends unchanged.

Scope note: like every ``serving.*`` metric, the stage gauge and
counters are process-global — several engines in one process share
the family (the AlertManager caveat, docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import time

from ..core import flags as flags_mod
from ..core import resilience
from ..profiler import metrics as _metrics
from ..testing import faults as _faults

__all__ = ["AdmissionRejected", "ServiceTimeModel", "BrownoutController",
           "OverloadController", "NULL", "HIGH", "NORMAL", "LOW"]

# priority classes: smaller = more important (any int is accepted; these
# are the named rungs the brownout ladder gates against)
HIGH = 0
NORMAL = 1
LOW = 2

_US_BOUNDS = (500, 1000, 2500, 5000, 10000, 25000, 50000, 100000,
              250000, 500000, 1000000, 5000000)
_m_adm_rejected = _metrics.counter("serving.admission.rejected")
_m_clamped = _metrics.counter("serving.brownout.clamped")
_m_transitions = _metrics.counter("serving.brownout.transitions")
_g_stage = _metrics.gauge("serving.brownout.stage")
_h_pred_ttft = _metrics.histogram("admission.predicted_ttft_us",
                                  bounds=_US_BOUNDS)


class AdmissionRejected(RuntimeError):
    """Submission refused by the overload control plane — before any
    queueing or prefill. Structured like the new ``QueueFullError``:
    the caller (or the router) reads the fields instead of parsing the
    message. ``reason`` is ``"deadline"`` (the EWMA model proved the
    deadline unmeetable) or ``"brownout"`` (the ladder's current stage
    rejects this priority class); ``retry_after_s`` estimates when a
    retry could be admitted (None when the model is unprimed)."""

    def __init__(self, message, *, reason, retry_after_s=None,
                 predicted_ttft_s=None, deadline_s=None,
                 queue_depth=None, priority=None, stage=None):
        super().__init__(message)
        self.reason = reason
        self.retry_after_s = retry_after_s
        self.predicted_ttft_s = predicted_ttft_s
        self.deadline_s = deadline_s
        self.queue_depth = queue_depth
        self.priority = priority
        self.stage = stage


class ServiceTimeModel:
    """EWMA service-time model: per-token prefill cost and per-step
    decode cost, observed COMPILE-FREE (the scheduler subtracts the
    per-thread compile-seconds delta around each dispatch, the
    accounting discipline) so one cold bucket never poisons the
    steady-state estimate. Predictions are deliberately simple and
    documented — a drain-time estimate, not a simulation — and the
    admission path divides by ``FLAGS_admission_optimism`` worth of
    slack before trusting them.

    One stalled dispatch is not a service time: once primed, a sample
    counts for at most ``OUTLIER`` times the estimate it updates. A
    single step that a paused host stretched to seconds would otherwise
    raise the decode estimate by a fifth of the stall and, times a queue
    of 64, predict a wait past ``FLAGS_shed_wait_s``: half the queue shed
    for one hiccup (the driver's check of PR 28). A slowdown that lasts
    still moves the estimate by ``1 + alpha * (OUTLIER - 1)`` a sample
    (1.6 at the defaults) until the samples are within ``OUTLIER`` of it:
    a step ten times slower for good reads ninefold after 11 samples
    instead of 10, so sustained overload sheds as before."""

    OUTLIER = 4.0

    __slots__ = ("alpha", "min_samples", "prefill_us_per_token",
                 "decode_step_us", "n_prefill", "n_decode")

    def __init__(self, alpha=0.2, min_samples=3):
        self.alpha = float(alpha)
        self.min_samples = int(min_samples)
        self.prefill_us_per_token = None
        self.decode_step_us = None
        self.n_prefill = 0
        self.n_decode = 0

    @property
    def primed(self):
        """Enough observations to base a REJECTION on. Predictions are
        served regardless (the histogram wants them); refusals wait."""
        return self.n_prefill >= self.min_samples

    def _ewma(self, old, sample, seen):
        if old is None:
            return sample
        if seen >= self.min_samples and old > 0.0:
            sample = min(sample, self.OUTLIER * old)
        return old + self.alpha * (sample - old)

    def observe_prefill(self, tokens, us):
        """One prefill dispatch computed ``tokens`` (padded) in ``us``
        of compile-free wall time."""
        rate = float(us) / max(int(tokens), 1)
        self.prefill_us_per_token = \
            self._ewma(self.prefill_us_per_token, rate, self.n_prefill)
        self.n_prefill += 1

    def observe_decode(self, us):
        """One batched decode step took ``us`` compile-free."""
        self.decode_step_us = self._ewma(self.decode_step_us, float(us),
                                         self.n_decode)
        self.n_decode += 1

    def predict(self, queued_tokens, queued_requests, own_tokens):
        """(predicted queue-wait us, predicted TTFT us) for a request
        arriving behind ``queued_requests`` requests totalling
        ``queued_tokens`` estimated-uncovered prefill tokens, itself
        needing ``own_tokens``. Queue drain = everyone ahead's prefill
        plus one interleaved decode step per queued request (the
        budgeted-admission cadence); TTFT adds this request's own
        prefill and its first decode interleave."""
        ppt = self.prefill_us_per_token or 0.0
        step = self.decode_step_us or 0.0
        wait_us = queued_tokens * ppt + queued_requests * step
        ttft_us = wait_us + max(own_tokens, 1) * ppt + step
        return wait_us, ttft_us


class BrownoutController:
    """The ordered degradation ladder: stage 0 (normal) .. 3 (top
    priority only). Edge-triggered with hysteresis — escalation needs
    ``enter_steps`` CONSECUTIVE over-pressure updates, de-escalation
    ``exit_steps`` consecutive updates at or below ``exit_pressure``,
    and the band between exit_pressure and 1.0 holds the stage (both
    counters reset on any interruption, so a flapping signal never
    walks the ladder). Each transition moves the
    ``serving.brownout.stage`` gauge, counts
    ``serving.brownout.transitions``, and lands a flight record, so a
    post-mortem shows exactly when service degraded and recovered."""

    MAX_STAGE = 3

    __slots__ = ("enter_steps", "exit_steps", "exit_pressure", "stage",
                 "_over", "_under")

    def __init__(self, enter_steps=None, exit_steps=None,
                 exit_pressure=None):
        self.enter_steps = (
            int(flags_mod.flag("FLAGS_brownout_enter_steps"))
            if enter_steps is None else int(enter_steps))
        self.exit_steps = (
            int(flags_mod.flag("FLAGS_brownout_exit_steps"))
            if exit_steps is None else int(exit_steps))
        self.exit_pressure = (
            float(flags_mod.flag("FLAGS_brownout_exit_pressure"))
            if exit_pressure is None else float(exit_pressure))
        self.stage = 0
        self._over = 0
        self._under = 0

    def update(self, pressure):
        """One evaluation (the scheduler calls it per step). Returns
        the (possibly changed) stage."""
        if pressure >= 1.0:
            self._under = 0
            self._over += 1
            if self._over >= self.enter_steps \
                    and self.stage < self.MAX_STAGE:
                self._transition(self.stage + 1, pressure)
                self._over = 0
        elif pressure <= self.exit_pressure:
            self._over = 0
            self._under += 1
            if self._under >= self.exit_steps and self.stage > 0:
                self._transition(self.stage - 1, pressure)
                self._under = 0
        else:
            # hysteresis band: hold the stage, restart both windows
            self._over = 0
            self._under = 0
        return self.stage

    def _transition(self, to, pressure):
        frm, self.stage = self.stage, to
        _g_stage.set(to)
        _m_transitions.inc()
        try:
            from ..distributed import watchdog
            watchdog.record_event(
                "brownout.stage",
                meta={"from": frm, "to": to,
                      "pressure": round(float(pressure), 3)},
                status="degraded" if to > frm else "recovered")
        except Exception:  # noqa: BLE001 — telemetry must not block control
            pass


class OverloadController:
    """Per-scheduler control plane: owns the service-time model, the
    pressure computation, the shed policy, and (optionally) the
    brownout ladder. The scheduler drives it: ``observe_*`` at each
    dispatch, ``control`` once per step (before admission), ``admit``
    at each submit. NOT thread-safe by itself — the frontend's engine
    lock serializes, like the Accountant."""

    armed = True

    def __init__(self, admission=True, brownout=True, model=None):
        self.shedding = bool(admission)
        self.model = model if model is not None else ServiceTimeModel()
        self.optimism = float(flags_mod.flag("FLAGS_admission_optimism"))
        self.min_queue = int(flags_mod.flag("FLAGS_shed_min_queue"))
        self.queue_frac = float(flags_mod.flag("FLAGS_shed_queue_frac"))
        self.kv_frac = float(flags_mod.flag("FLAGS_shed_kv_frac"))
        self.wait_s = float(flags_mod.flag("FLAGS_shed_wait_s"))
        self.clamp_tokens = int(
            flags_mod.flag("FLAGS_brownout_clamp_tokens"))
        self.brownout = BrownoutController() if brownout else None

    # -- scheduler hooks ---------------------------------------------------

    def observe_prefill(self, tokens, us):
        self.model.observe_prefill(tokens, us)

    def observe_decode(self, us):
        self.model.observe_decode(us)

    def estimate_tokens(self, sched, prompt):
        """Estimated tokens this prompt will actually COMPUTE at
        prefill — the prefix-cache plan's uncovered tail when caching
        is on (``plan_prefix`` is pure: no counters, no allocation), so
        a cache-hitting prompt predicts cheap, matching how admission
        will bill it."""
        if sched.prefix_cache:
            try:
                plan = sched.cache.plan_prefix(prompt)
                return max(len(prompt) - plan.covered_tokens, 1)
            except Exception:  # noqa: BLE001 — an estimate, never a failure
                pass
        return max(len(prompt), 1)

    def _queued_tokens(self, sched):
        return sum(r.est_tokens for r in sched.queue)

    def queue_retry_after(self, sched):
        """Predicted seconds until the current queue drains — the
        ``retry_after_s`` stamped on sheds and structured rejections.
        None until the model is primed (an unprimed estimate would be
        noise presented as advice)."""
        if not self.model.primed:
            return None
        wait_us, _ = self.model.predict(self._queued_tokens(sched),
                                        len(sched.queue), 0)
        return max(wait_us / 1e6, 0.001)

    def admit(self, sched, prompt, max_new_tokens, deadline, priority):
        """The submit-time gate. Returns ``(est_tokens,
        effective_max_new_tokens)`` or raises :class:`AdmissionRejected`
        (brownout priority floor, or a provably-unmeetable deadline).
        Prediction failures FAIL OPEN — a broken model must not refuse
        traffic the plain queue bound would have taken."""
        stage = self.brownout.stage if self.brownout is not None else 0
        if stage >= 1 and self.clamp_tokens \
                and max_new_tokens > self.clamp_tokens:
            max_new_tokens = self.clamp_tokens
            _m_clamped.inc()
        est = self.estimate_tokens(sched, prompt)
        wait_us = ttft_us = None
        if self.shedding:
            try:
                _faults.site("admission.predict")
                wait_us, ttft_us = self.model.predict(
                    self._queued_tokens(sched), len(sched.queue), est)
                _h_pred_ttft.observe(ttft_us)
            except Exception as e:  # noqa: BLE001 — fail open
                resilience.degrade("serving.admission", exc=e)
                wait_us = ttft_us = None
        floor = HIGH if stage >= 3 else (NORMAL if stage >= 2 else None)
        if floor is not None and priority > floor:
            _m_adm_rejected.inc()
            raise AdmissionRejected(
                f"serving.submit: brownout stage {stage} admits only "
                f"priority <= {floor} (got {priority})",
                reason="brownout", stage=stage, priority=priority,
                queue_depth=len(sched.queue),
                retry_after_s=None if wait_us is None
                else max(wait_us / 1e6, 0.001))
        if deadline is not None and ttft_us is not None \
                and self.model.primed:
            remaining = deadline.remaining()
            predicted_s = ttft_us / 1e6
            if predicted_s * self.optimism > remaining:
                _m_adm_rejected.inc()
                raise AdmissionRejected(
                    f"serving.submit: deadline provably unmeetable — "
                    f"predicted TTFT {predicted_s * 1e3:.1f}ms (even "
                    f"x{self.optimism} optimism) exceeds the "
                    f"{remaining * 1e3:.1f}ms remaining",
                    reason="deadline", predicted_ttft_s=predicted_s,
                    deadline_s=remaining, priority=priority,
                    queue_depth=len(sched.queue),
                    retry_after_s=max(wait_us / 1e6,
                                      predicted_s - remaining, 0.001))
        return est, max_new_tokens

    # -- the per-step control loop ----------------------------------------

    def pressure(self, sched):
        """Overload pressure in [0, inf): the max of the normalized
        watermark signals, gated on a real queued backlog
        (``FLAGS_shed_min_queue``) — pressure without demand is just a
        busy engine. >= 1.0 means shed territory."""
        q = len(sched.queue)
        if q < self.min_queue:
            return 0.0
        parts = [0.0]
        if sched.max_queue:
            # the backlog is what is queued beyond the free decode
            # slots: a request a slot stands free for is an admission
            # that has not happened yet (a closed loop opens with all
            # its clients queued at once on an idle engine), not load
            # the engine cannot take
            backlog = q - (sched.cache.max_batch - len(sched.running))
            parts.append(backlog / max(self.queue_frac * sched.max_queue,
                                       1.0))
        # mesh-sliced caches: the KV watermark reads the BINDING slice
        # (the one the next admission would land on) — aggregate
        # headroom is a lie when the binding slice is full. Unsliced
        # caches return None -> the aggregate, byte-for-byte pre-mesh.
        occ = sched.cache.occupancy(slice=sched.cache.binding_slice())
        if occ["usable"]:
            parts.append((occ["active"] / occ["usable"]) / self.kv_frac)
        if self.model.primed:
            wait_us, _ = self.model.predict(self._queued_tokens(sched),
                                            q, 0)
            parts.append((wait_us / 1e6) / self.wait_s)
        return max(parts)

    def _shed_victim(self, queue):
        """Lowest-priority, newest queued request — never the top
        class (watermark shedding protects priority HIGH outright; only
        the brownout ladder's stage 3 can refuse everything else), and
        never a PREEMPTED request: it already streamed tokens to its
        caller (the SHED contract is "streamed nothing, retry safely"),
        and its device work is sunk cost worth finishing."""
        victim = None
        for r in queue:
            if r.priority <= HIGH or r.generated:
                continue
            if victim is None \
                    or (r.priority, r.rid) > (victim.priority, victim.rid):
                victim = r
        return victim

    def control(self, sched):
        """One per-step evaluation: compute pressure, walk the brownout
        ladder, shed queued requests while over pressure. Returns the
        pressure it acted on."""
        p = self.pressure(sched)
        if self.brownout is not None:
            self.brownout.update(p)
        if not self.shedding:
            return p
        while p >= 1.0 and sched.queue:
            victim = self._shed_victim(sched.queue)
            if victim is None:
                break
            sched.shed(victim,
                       retry_after_s=self.queue_retry_after(sched))
            p = self.pressure(sched)
        return p


class _NullOverload(OverloadController):
    """Disarmed control plane: every scheduler hook a no-op (the
    nearly-free-when-off contract — tools/overload_gate.py pins the
    byte-for-byte revert and counter silence)."""

    armed = False
    shedding = False
    brownout = None

    def __init__(self):  # no flag reads, no model
        pass

    def observe_prefill(self, tokens, us):
        pass

    def observe_decode(self, us):
        pass

    def admit(self, sched, prompt, max_new_tokens, deadline, priority):
        return 0, max_new_tokens

    def control(self, sched):
        return 0.0

    def queue_retry_after(self, sched):
        return None


NULL = _NullOverload()
