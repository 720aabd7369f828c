"""Share of the HBM bandwidth roofline the paged decode attention
kernel reached in the traced slice. The bytes a kernel call must read
are the context of every live sequence, K and V: the mean
``context_tokens`` of the slice's ``serving.decode.dispatch`` spans x 2 x
kv heads x head size x the pool's item size (from the cell's
configuration). Over the mean device time of a ``paged_decode*`` call in
the slice and the chip's HBM bytes a second. Means on both sides, so a
step the slice's edge cuts does not skew it. Useful bytes only (not the
pages a chunk pads to), so it cannot pass 100."""

from benchmarks import span_reduce


def read(ctx):
    import jax.numpy as jnp

    trace, spans = ctx.get("trace"), span_reduce.of_cell(ctx)
    if not trace or not spans or not spans["decode_dispatches"] \
            or ctx["peaks"] is None:
        return None
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if "paged_decode" in name)
    calls = sum(n for name, n in trace["op_counts"].items()
                if "paged_decode" in name)
    if not seconds or not calls:
        return None
    fields = ctx["cell"].config
    head = fields["hidden_size"] // fields["num_attention_heads"]
    itemsize = jnp.dtype(fields["torch_dtype"]).itemsize
    tokens = spans["decode_context_tokens"] / spans["decode_dispatches"]
    nbytes = tokens * 2 * fields["num_key_value_heads"] * head * itemsize
    return 100.0 * nbytes / (seconds / calls) / ctx["peaks"]["hbm_bytes_per_s"]
