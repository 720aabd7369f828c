"""Share of the HBM bandwidth roofline the grouped expert matmuls of the
block steps reached (``moe_gmm_swiglu_step`` + ``moe_gmm_step``, a pair a
layer). The bytes a layer has to move (``ops_count_moe``): the three
matrices of every expert that got a row, and the rows' activations; the
experts hit and the rows are the window's means a layer of a step
(``serving.moe.experts_hit``, ``serving.moe.rows`` over the block steps x
layers). Over the mean device time of a pair in the slice and the chip's
HBM bytes a second. Useful bytes only, so it cannot pass 100."""

from benchmarks import ops_count_moe


def read(ctx):
    import jax.numpy as jnp

    trace, counters = ctx.get("trace"), ctx["counters"]
    steps = (counters.get("serving.phase.decode_dispatch_us")
             or {}).get("count", 0)
    if not trace or not steps or ctx["peaks"] is None \
            or "serving.moe.rows" not in counters:
        return None
    step_ops = {name: s for name, s in trace["op_seconds"].items()
                if "moe_gmm" in name and name.endswith("_step")}
    pairs = sum(n for name, n in trace["op_counts"].items()
                if name.startswith("moe_gmm_swiglu")
                and name.endswith("_step"))
    if not step_ops or not pairs:
        return None
    fields = ctx["cell"].config
    calls = steps * fields["num_hidden_layers"]
    nbytes = ops_count_moe.expert_layer_bytes(
        counters["serving.moe.experts_hit"] / calls,
        counters["serving.moe.rows"] / calls, fields,
        jnp.dtype(fields["torch_dtype"]).itemsize)
    seconds = sum(step_ops.values()) / pairs
    return 100.0 * nbytes / seconds / ctx["peaks"]["hbm_bytes_per_s"]
