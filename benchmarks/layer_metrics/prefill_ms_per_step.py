"""Milliseconds of a scheduler step inside the model's prefill calls:
the sums of ``serving.phase.prefill_forward_us``, ``..pool_write_us`` and
``..readback_us`` (``models/llama.py paged_prefill*``) over the steps of
the window. With ``decode_ms_per_step`` and ``host_ms_per_step`` it adds
up to ``sched_step_mean_ms``."""

from benchmarks import span_reduce


def read(ctx):
    return span_reduce.phase_ms_per_step(ctx, *span_reduce.PREFILL_PHASES)
