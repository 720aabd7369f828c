"""Model FLOP/s utilization of a hybrid model's whole decode step: the
useful FLOPs of a step (``ops_count_ssm.decode_step_flops``: 2 a
parameter a row multiplies, the recurrence of the state-space layers and
the attention's scores and values) over the mean device time of a
``jit_jamba_paged_decode`` event wholly inside the slice, over the chip's
bf16 peak. The rows and the context tokens of a step are the window's
means: ``serving.ssm.state_slot_steps`` and
``serving.decode.context_tokens`` over the decode dispatches. A step of
128 rows is bound by bytes (``decode_step_hbm_roofline``), so this share
is low by design; it is the share of the whole step that bounds any
later claim in this cell."""

from benchmarks import ops_count_ssm, span_reduce

MODULE = "jit_jamba_paged_decode"


def step_means(ctx):
    """(mean seconds of a whole decode program in the slice, mean live
    rows a step, mean context tokens a step), or None where there is
    nothing to read."""
    spans, counters = span_reduce.of_cell(ctx), ctx["counters"]
    steps = (counters.get("serving.phase.decode_dispatch_us")
             or {}).get("count", 0)
    rows = counters.get("serving.ssm.state_slot_steps", 0)
    if not spans or not steps or not rows or ctx["peaks"] is None:
        return None
    events, seconds = spans["whole_modules"].get(MODULE, (0, 0.0))
    if not events:
        return None
    return (seconds / events, rows / steps,
            counters.get("serving.decode.context_tokens", 0) / steps)


def read(ctx):
    means = step_means(ctx)
    if means is None:
        return None
    seconds, rows, context = means
    flops = ops_count_ssm.decode_step_flops(ctx["cell"].config, rows,
                                            context)
    return 100.0 * flops / seconds / ctx["peaks"]["bf16_flops_per_s"]
