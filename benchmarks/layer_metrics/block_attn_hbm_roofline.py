"""Share of the HBM bandwidth roofline the block attention kernel reached
in the traced slice (``custom-call``s named ``paged_block*``): as
``paged_attn_hbm_roofline``, with the configuration's own ``head_dim``
(not hidden / heads). The bytes a call must read are K and V of every
live slot's context, the block's own rows among them: the mean
``context_tokens`` of the slice's ``serving.decode.dispatch`` spans x 2 x
kv heads x head_dim x item size (``ops_count_moe.attention_bytes``), over
the mean device time of a call and the chip's HBM bytes a second.
Useful bytes only, so it cannot pass 100."""

from benchmarks import ops_count_moe, span_reduce


def read(ctx):
    import jax.numpy as jnp

    trace, spans = ctx.get("trace"), span_reduce.of_cell(ctx)
    if not trace or not spans or not spans["decode_dispatches"] \
            or ctx["peaks"] is None:
        return None
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if "paged_block" in name)
    calls = sum(n for name, n in trace["op_counts"].items()
                if "paged_block" in name)
    if not seconds or not calls:
        return None
    fields = ctx["cell"].config
    nbytes = ops_count_moe.attention_bytes(
        spans["decode_context_tokens"] / spans["decode_dispatches"], fields,
        jnp.dtype(fields["torch_dtype"]).itemsize)
    return 100.0 * nbytes / (seconds / calls) / ctx["peaks"]["hbm_bytes_per_s"]
