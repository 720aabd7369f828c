"""Prefill length bucketing: bounded warm jit-cache footprint.

The prefill program is traced per padded prompt shape, so serving raw
lengths compiles an unbounded set of XLA executables (one per distinct
block-multiple length) — a production killer: every novel prompt length
pays a multi-second compile mid-serve. Bucketing rounds the padded
length up to the next power of two (capped by
``FLAGS_serving_prefill_bucket_cap``), so at most ``log2(cap)`` prefill
programs exist after warmup, whatever traffic arrives.

The extra padding is dead compute only: positions past the true length
are masked in attention, and pool writes past the slot's allocated
blocks land in the reserved null block 0 (see
``Llama.paged_prefill``). Lengths beyond the cap fall back to plain
block-multiple padding (they are rare by construction — cap at your p99
prompt length).

Pinned by the compile-count test in tests/framework/test_serving.py and
the no-recompile check in tools/serving_gate.py, both via the
``xla.compile.count`` metric (profiler.metrics' jax.monitoring
listener).

Interaction with prefix caching (``FLAGS_serving_prefix_cache``):
chunk hashes are computed over the UNPADDED token ids before any
bucketing — padding must never poison a content hash, or two prompts
that merely share a bucket would alias. The padded KV the prefill
writes past the true length is garbage but harmless: every reader
masks by seq_len, sharers of a partially-filled block copy-on-write
before their own tokens land, and decode appends overwrite those rows
in place. Cache-hitting admissions bucket only their uncovered TAIL
(the covered prefix is mapped, not computed), so the warm program set
stays bounded by the same log2(cap) ladder.
"""

from __future__ import annotations

__all__ = ["bucket_length", "bucket_lengths"]


def _round_up(n, multiple):
    return -(-n // multiple) * multiple


def bucket_length(n_tokens, block_size, cap, max_len=None):
    """Padded prefill length for a prompt of ``n_tokens``.

    Power-of-two bucket >= n_tokens (and >= block_size), rounded up to a
    block multiple, as long as the bucket fits under ``cap``; otherwise
    the plain block-multiple pad. ``max_len`` (the cache's
    max_blocks_per_seq * block_size) clamps the result either way.
    """
    if n_tokens < 1:
        raise ValueError(f"bucket_length: n_tokens must be >= 1, "
                         f"got {n_tokens}")
    base = _round_up(n_tokens, block_size)
    out = base
    if cap and cap > 0:
        p = max(block_size, 1)
        while p < n_tokens:
            p <<= 1
        p = _round_up(max(p, base), block_size)
        if p <= cap:
            out = p
    if max_len is not None:
        # clamp to the cache's capacity, but never below the minimal
        # block-multiple pad (callers validate n_tokens <= max_len)
        out = max(min(out, _round_up(max_len, block_size)), base)
    return out


def bucket_lengths(block_size, cap, max_len):
    """Every bucket a serving config can produce, ascending: the
    ladder up to the cap, then every block multiple past it.
    ``ServingEngine.warmup`` prefills through those up to the cap, so
    that live traffic under the cap never compiles."""
    out, seen = [], set()
    n = 1
    while n <= max_len:
        b = bucket_length(n, block_size, cap, max_len)
        if b not in seen:
            seen.add(b)
            out.append(b)
        n = b + 1
    return out
