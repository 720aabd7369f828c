"""The one traffic generator: every mix is a data file of parameters
under ``traffic/`` that this module turns into requests or batches.

Everything is a pure function of ``(seed, index)``. The work is the same
for every seed: a mix fixes a *cycle* of ``n`` lengths and gaps, the
stratified quantiles ``(i + 0.5) / n`` of its distributions in an order
that belongs to the mix (``order_seed``); the seed draws only the token
ids (and the weights). An open loop's ``"cycle": "window"`` makes the
cycle as long as the window (rate x seconds requests, whose gaps sum to
the window). The schedule is the mix's and not the seed's so that no
seed changes the work: a tail that queueing makes hangs on which requests
meet (with the same cycle entered at a phase drawn from the seed, one
seed of six read the 95th percentile of time to first token as 291 ms
against 464 to 515 ms for the others; chip runs, PR 24).

Copied from ``paddle_tpu/serving/loadgen.py`` (``bounded_pareto``, the
per-(seed, index, salt) PCG64 streams, the burst shape); that module's
i.i.d. draws and its ``replay()`` are not used (PERF.md, inventory).
"""

from __future__ import annotations

import math

import numpy as np

# one stream per purpose, so a new knob never perturbs another's draws
_SALT_PROMPT, _SALT_OUTPUT, _SALT_GAP, _SALT_TOKENS = 1, 2, 3, 4
_SALT_SHARED, _SALT_PREFIX, _SALT_BATCH = 5, 6, 7

# ids below this are left to special tokens, as loadgen.prompt_ids does
_FIRST_TOKEN_ID = 3


def _rng(seed, salt, index):
    return np.random.default_rng([int(seed), int(salt), int(index)])


def bounded_pareto(u, alpha, lo, hi):
    """Inverse CDF of the bounded Pareto on [lo, hi] (smaller ``alpha``
    = heavier tail) at ``u`` in (0, 1)."""
    lo, hi = float(lo), float(hi)
    if hi <= lo:
        return lo
    la, ha = lo ** alpha, hi ** alpha
    return (-(u * ha - u * la - ha) / (ha * la)) ** (-1.0 / alpha)


def quantile(dist, u):
    """Value of the length/gap distribution ``dist`` (a dict from a
    traffic file) at quantile ``u``."""
    kind = dist["dist"]
    if kind == "fixed":
        return float(dist["value"])
    if kind == "uniform":
        return dist["lo"] + u * (dist["hi"] - dist["lo"])
    if kind == "bounded_pareto":
        return bounded_pareto(u, dist["alpha"], dist["lo"], dist["hi"])
    if kind == "exponential":
        return -math.log(1.0 - u) * dist["mean"]
    raise ValueError(f"unknown distribution {kind!r}")


class RequestMix:
    """Requests of a serving mix. ``request(i)`` -> (prompt ids, number of
    new tokens); ``due_offsets(count)`` -> seconds from the start of the
    generator at which open-loop requests are due. ``seconds`` is the
    window's length, which ``"cycle": "window"`` needs."""

    def __init__(self, params, seed, vocab_size, seconds=None):
        self.p = params
        self.seed = int(seed)
        self.vocab = int(vocab_size)
        if params["cycle"] == "window":
            self.n = int(round(params["arrivals"]["rate_rps"] * seconds))
        else:
            self.n = int(params["cycle"])
        self._cycles = {}

    def _value(self, dist, salt, index, n=None, mean=None):
        """The ``index``-th draw of ``dist``: the mix's fixed order of
        its ``n`` stratified quantiles, round and round. ``mean``
        rescales the cycle to that mean exactly (the midpoint quantiles
        of a long tail fall a little short of it)."""
        n = n or self.n
        key = (salt, n)
        if key not in self._cycles:
            order = _rng(self.p.get("order_seed", 0), salt, n).permutation(n)
            values = np.array([quantile(dist, (k + 0.5) / n) for k in order])
            if mean is not None:
                values *= mean / values.mean()
            self._cycles[key] = values
        return float(self._cycles[key][int(index) % n])

    def lengths(self, i):
        plen = int(round(self._value(self.p["prompt_len"], _SALT_PROMPT, i)))
        olen = int(round(self._value(self.p["output_len"], _SALT_OUTPUT, i)))
        return max(plen, 1), max(olen, 1)

    def request(self, i):
        plen, olen = self.lengths(i)
        ids = _rng(self.seed, _SALT_TOKENS, i).integers(
            _FIRST_TOKEN_ID, self.vocab, size=plen)
        shared = self.p.get("shared_prefix")
        if shared and shared["share"] > 0:
            # a stratified share of each cycle opens with one of a few
            # common prefixes (system prompts); the rest stay distinct
            u = self._value({"dist": "uniform", "lo": 0.0, "hi": 1.0},
                            _SALT_SHARED, i)
            if u < shared["share"]:
                which = i % int(shared["prefixes"])
                k = min(int(shared["len"]), plen - 1)
                ids[:k] = _rng(self.seed, _SALT_PREFIX, which).integers(
                    _FIRST_TOKEN_ID, self.vocab, size=int(shared["len"]))[:k]
        return ids.astype(np.int64), olen

    def due_offsets(self, count):
        """Open loop: the first ``count`` due times. ``poisson`` spaces
        single requests by exponential gaps of mean 1/rate; ``burst``
        spaces groups of ``burst_size`` by gaps of mean burst_size/rate,
        the group arriving together."""
        arr = self.p["arrivals"]
        rate = float(arr["rate_rps"])
        group = int(arr.get("burst_size", 1)) \
            if arr["process"] == "burst" else 1
        if arr["process"] not in ("poisson", "burst"):
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        gap = {"dist": "exponential", "mean": group / rate}
        groups = max(self.n // group, 1)  # gaps in a cycle
        out, t = [], 0.0
        for g in range(-(-int(count) // group)):
            t += self._value(gap, _SALT_GAP, g, n=groups, mean=gap["mean"])
            out.extend([t] * group)
        return out[:int(count)]


def train_batches(params, seed, vocab_size):
    """The distinct batches of a training mix, [batch, seq_len] int64
    each, that the driver cycles through."""
    return [_rng(seed, _SALT_BATCH, i).integers(
                0, int(vocab_size),
                size=(int(params["batch"]), int(params["seq_len"]))
            ).astype(np.int64)
            for i in range(int(params["distinct_batches"]))]
