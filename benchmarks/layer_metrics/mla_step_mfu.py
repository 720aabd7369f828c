"""Model FLOP/s utilization of a latent-attention model's whole decode
step: the useful FLOPs of a step (``ops_count_mla.decode_step_flops``: 2
a parameter a row multiplies, attention in its absorbed form, the stream
maps, the router, the routed and shared experts or the dense SwiGLU, the
head; and the attention's scores and values over the context) over the
mean device time of a ``jit_xing_paged_decode`` event wholly inside the
slice, over the chip's bf16 peak. The rows, the context tokens and the
experts hit of a step are the window's means: ``serving.moe.rows`` over
the sparse layers and the experts a token, ``serving.decode.
context_tokens`` and ``serving.moe.experts_hit`` over the decode
dispatches. A step of 128 rows is bound by bytes
(``mla_step_hbm_roofline``), so this share is low by design; it is the
share of the whole step that bounds any later claim in this cell."""

from benchmarks import ops_count_mla, span_reduce

MODULE = "jit_xing_paged_decode"


def step_means(ctx):
    """(mean seconds of a whole decode program in the slice, mean live
    rows a step, mean context tokens a step, mean experts hit a sparse
    layer of a step), or None where there is nothing to read."""
    spans, counters = span_reduce.of_cell(ctx), ctx["counters"]
    steps = (counters.get("serving.phase.decode_dispatch_us")
             or {}).get("count", 0)
    routed = counters.get("serving.moe.rows", 0)
    if not spans or not steps or not routed or ctx["peaks"] is None:
        return None
    events, seconds = spans["whole_modules"].get(MODULE, (0, 0.0))
    if not events:
        return None
    s = ops_count_mla.shapes(ctx["cell"].config)
    sparse = max(s["sparse_layers"], 1)
    return (seconds / events, routed / (sparse * s["top_k"]) / steps,
            counters.get("serving.decode.context_tokens", 0) / steps,
            counters.get("serving.moe.experts_hit", 0) / (sparse * steps))


def read(ctx):
    means = step_means(ctx)
    if means is None:
        return None
    seconds, rows, context, _ = means
    flops = ops_count_mla.decode_step_flops(ctx["cell"].config, rows,
                                            context)
    return 100.0 * flops / seconds / ctx["peaks"]["bf16_flops_per_s"]
