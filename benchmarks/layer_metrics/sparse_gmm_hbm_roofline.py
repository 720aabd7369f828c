"""Share of the HBM bandwidth roofline the grouped expert matmuls of the
plain decode steps reached (``moe_gmm_swiglu_decode`` + ``moe_gmm_decode``,
a pair a sparse layer), for a model whose leading layers are dense. The
bytes a sparse layer has to move (``ops_count_moe.expert_layer_bytes``):
the three matrices of every expert that got a row, and the rows'
activations; the experts hit and the rows are the window's means a sparse
layer of a step (``serving.moe.experts_hit``, ``serving.moe.rows`` over
the decode dispatches x (``num_hidden_layers`` -
``first_k_dense_replace``)). Over the mean device time of a pair in the
slice and the chip's HBM bytes a second. Useful bytes only, so it cannot
pass 100."""

from benchmarks import ops_count_moe


def read(ctx):
    import jax.numpy as jnp

    trace, counters = ctx.get("trace"), ctx["counters"]
    steps = (counters.get("serving.phase.decode_dispatch_us")
             or {}).get("count", 0)
    if not trace or not steps or ctx["peaks"] is None \
            or not counters.get("serving.moe.rows"):
        return None
    tagged = {name: s for name, s in trace["op_seconds"].items()
              if "moe_gmm" in name and name.endswith("_decode")}
    pairs = sum(n for name, n in trace["op_counts"].items()
                if name.startswith("moe_gmm_swiglu")
                and name.endswith("_decode"))
    if not tagged or not pairs:
        return None
    fields = ctx["cell"].config
    calls = steps * (fields["num_hidden_layers"]
                     - fields["first_k_dense_replace"])
    nbytes = ops_count_moe.expert_layer_bytes(
        counters["serving.moe.experts_hit"] / calls,
        counters["serving.moe.rows"] / calls, fields,
        jnp.dtype(fields["torch_dtype"]).itemsize)
    return 100.0 * nbytes / (sum(tagged.values()) / pairs) \
        / ctx["peaks"]["hbm_bytes_per_s"]
