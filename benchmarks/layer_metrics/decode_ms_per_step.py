"""Milliseconds of a scheduler step inside the batched decode call and
the wait for its tokens: the sums of ``serving.phase.decode_dispatch_us``
and ``..decode_readback_us`` (``serving/scheduler.py _decode``) over the
steps of the window."""

from benchmarks import span_reduce


def read(ctx):
    return span_reduce.phase_ms_per_step(ctx, *span_reduce.DECODE_PHASES)
