"""Model FLOP/s utilization of the block-step program: the useful FLOPs
of a step (``ops_count_moe.block_step_flops``: 2 a parameter a row
multiplies, the router and the ``num_experts_per_tok`` experts it is
routed to among them, and the attention's scores and values) over the
mean device time of a ``jit_sdar_block_step`` event wholly inside the
slice, over the chip's bf16 peak. The rows and the context tokens of a
step are the window's means: ``serving.moe.rows`` / (layers x experts a
token) and ``serving.decode.context_tokens`` over the block steps. The
share of the whole step that bounds any later claim in this cell."""

from benchmarks import ops_count_moe, span_reduce


def read(ctx):
    spans, counters = span_reduce.of_cell(ctx), ctx["counters"]
    steps = (counters.get("serving.phase.decode_dispatch_us")
             or {}).get("count", 0)
    if not spans or not steps or ctx["peaks"] is None \
            or "serving.moe.rows" not in counters:
        return None
    events, seconds = spans["whole_modules"].get("jit_sdar_block_step",
                                                 (0, 0.0))
    if not events:
        return None
    fields = dict(ctx["cell"].config,
                  block_length=ctx["cell"].traffic["block_length"])
    rows = counters["serving.moe.rows"] / (
        fields["num_hidden_layers"] * fields["num_experts_per_tok"]) / steps
    context = counters.get("serving.decode.context_tokens", 0) / steps
    flops = ops_count_moe.block_step_flops(rows, context, fields)
    return 100.0 * flops / (seconds / events) \
        / ctx["peaks"]["bf16_flops_per_s"]
