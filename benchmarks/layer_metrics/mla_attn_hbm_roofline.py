"""Share of the HBM bandwidth roofline the absorbed latent-attention
decode kernel reached in the traced slice. The bytes a call (one layer)
must read are the cached row of every context token of every live slot
(``ops_count_mla.attention_call_bytes``: latent + rotary values, once,
whatever the number of heads), at the mean ``context_tokens`` of the
slice's ``serving.decode.dispatch`` spans. Over the mean device time of
an ``mla_decode*`` call in the slice and the chip's HBM bytes a second.
Useful bytes only (not the lanes a rotary row is padded to, nor the
pages a chunk pads to), so it cannot pass 100; at 60 FLOP a byte the
kernel's roofline is HBM's."""

from benchmarks import ops_count_mla, span_reduce


def read(ctx):
    import jax.numpy as jnp

    trace, spans = ctx.get("trace"), span_reduce.of_cell(ctx)
    if not trace or not spans or not spans["decode_dispatches"] \
            or ctx["peaks"] is None:
        return None
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if "mla_decode" in name)
    calls = sum(n for name, n in trace["op_counts"].items()
                if "mla_decode" in name)
    if not seconds or not calls:
        return None
    fields = ctx["cell"].config
    tokens = spans["decode_context_tokens"] / spans["decode_dispatches"]
    nbytes = ops_count_mla.attention_call_bytes(
        fields, tokens, jnp.dtype(fields["torch_dtype"]).itemsize)
    return 100.0 * nbytes / (seconds / calls) \
        / ctx["peaks"]["hbm_bytes_per_s"]
