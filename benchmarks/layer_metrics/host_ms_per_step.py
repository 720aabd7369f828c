"""Milliseconds of a scheduler step outside the model's calls: the sum
of ``serving.step_us`` less the prefill and decode phases, over the
steps of the window. What is left is the scheduler's own Python: sweep,
overload control, admission planning and finishing, decode preparation,
the per-request emit loop."""

from benchmarks import span_reduce


def read(ctx):
    model = span_reduce.phase_ms_per_step(
        ctx, *span_reduce.PREFILL_PHASES, *span_reduce.DECODE_PHASES)
    step = ctx["counters"].get("serving.step_us")
    if model is None:
        return None
    return step["sum"] / step["count"] / 1e3 - model
