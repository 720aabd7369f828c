"""The load generator of the serving cells: one thread that submits, and
the engine's own ``on_token`` callback that stamps each token as the
client receives it. No thread per client, so the generator takes little
of the CPU it shares with the server.

Closed loop: ``clients`` requests are kept in flight; a client sends its
next request when the last one ended. Open loop: requests are sent at
the due times the traffic mix fixes, whatever the server does, and every
latency is counted from the *due* time, so a stalled server delays the
requests behind the stall and a starved generator shows as lateness
(``late`` = sent - due), not as a fast server.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time

# terminal request statuses of ServingEngine (serving.scheduler.RequestStatus)
_ENDED = ("DONE", "CANCELLED", "TIMEOUT", "SHED", "ERROR")
_POLL_S = 0.05


class Record:
    """One request as the client saw it. Times are ``time.perf_counter``
    seconds."""

    __slots__ = ("index", "due", "sent", "n_new", "times", "handle",
                 "refused", "cancelled")

    def __init__(self, index, due, n_new):
        self.index = index
        self.due = due
        self.sent = None
        self.n_new = n_new
        self.times = []
        self.handle = None
        self.refused = None
        self.cancelled = False

    @property
    def complete(self):
        return len(self.times) == self.n_new

    @property
    def ended(self):
        """Complete, refused at submit, or ended early by the server."""
        return (self.complete or self.refused is not None
                or (self.handle is not None
                    and str(self.handle.status) in _ENDED))


class Client:
    """Drives ``engine`` with ``mix`` (a traffic.RequestMix) from one
    thread. ``start()``, then ``stop()`` ends submission; ``wait()``
    waits for what is in flight."""

    def __init__(self, engine, mix, annotate=None, sample=None):
        self.engine = engine
        self.mix = mix
        self.records = []
        self.samples = []          # (time, sample()) while running
        self._sample = sample
        # context-manager factory naming a span in the profiler's trace
        self._annotate = annotate or (lambda _name: contextlib.nullcontext())
        self._ended = queue.Queue()
        self._stop = threading.Event()
        self._thread = None
        self.error = None
        self.started_at = None

    # -- submission ----------------------------------------------------

    def _submit(self, index, due):
        prompt, n_new = self.mix.request(index)
        rec = Record(index, due, n_new)
        times = rec.times
        ended = self._ended

        def on_token(_tok):
            times.append(time.perf_counter())
            if len(times) == n_new:
                ended.put(rec)

        self.records.append(rec)
        rec.sent = time.perf_counter()
        if rec.due is None:
            rec.due = rec.sent
        try:
            with self._annotate("client.submit"):
                rec.handle = self.engine.submit(
                    prompt, max_new_tokens=n_new, on_token=on_token)
        except Exception as e:  # noqa: BLE001 — a refusal is a result
            rec.refused = repr(e)
        return rec

    def _take_sample(self):
        if self._sample is not None:
            self.samples.append((time.perf_counter(), self._sample()))

    def _closed_loop(self):
        inflight = set()
        nxt = 0
        for _ in range(int(self.mix.p["clients"])):
            inflight.add(self._submit(nxt, None))
            nxt += 1
        while not self._stop.is_set():
            self._take_sample()
            done = []
            try:
                done.append(self._ended.get(timeout=_POLL_S))
                while True:
                    done.append(self._ended.get_nowait())
            except queue.Empty:
                pass
            # a request the server ended early never reports through
            # on_token: find it by its status, at the polling interval
            done.extend(r for r in inflight
                        if r not in done and not r.complete and r.ended)
            for rec in done:
                inflight.discard(rec)
                inflight.add(self._submit(nxt, None))
                nxt += 1

    def _open_loop(self, horizon_s):
        offsets = []
        count = 64
        while not offsets or offsets[-1] <= horizon_s:
            offsets = self.mix.due_offsets(count)
            count *= 2
        t0 = self.started_at
        next_sample = t0
        for i, off in enumerate(offsets):
            due = t0 + off
            while True:
                now = time.perf_counter()
                if now >= next_sample:
                    self._take_sample()
                    next_sample = now + _POLL_S
                wait = due - now
                if wait <= 0 or self._stop.is_set():
                    break
                time.sleep(min(wait, _POLL_S))
            if self._stop.is_set():
                return
            self._submit(i, due)

    def _run(self, horizon_s):
        try:
            if self.mix.p["loop"] == "closed":
                self._closed_loop()
            elif self.mix.p["loop"] == "open":
                self._open_loop(horizon_s)
            else:
                raise ValueError(f"unknown loop {self.mix.p['loop']!r}")
        except BaseException as e:  # noqa: BLE001 — re-raised by wait()
            self.error = e

    def start(self, horizon_s):
        """Begin submitting. ``horizon_s``: how long the open loop's
        schedule has to reach (the closed loop runs until ``stop()``)."""
        self.started_at = time.perf_counter()
        self._thread = threading.Thread(
            target=self._run, args=(horizon_s,), name="bench-client",
            daemon=True)
        self._thread.start()

    def stop(self):
        """End submission. A closed loop always has requests in flight
        when it is stopped: the client withdraws them (``cancelled``),
        as a batch job that is stopped does; they are not judged."""
        self._stop.set()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError("the load generator did not stop")
        if self.error is not None:
            raise self.error
        if self.mix.p["loop"] == "closed":
            for rec in self.records:
                if not rec.ended:
                    rec.cancelled = True
                    rec.handle.cancel()

    def wait(self, limit_s):
        """Wait until every sent request ended or ``limit_s`` passed;
        returns the seconds waited."""
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < limit_s:
            if all(r.ended for r in self.records):
                break
            time.sleep(_POLL_S)
        return time.perf_counter() - t0
