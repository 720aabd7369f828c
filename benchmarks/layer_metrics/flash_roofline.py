"""Share of their roofline that the causal flash attention kernels
(``flash_fwd``, ``flash_dq``, ``flash_dkv``) reached in the traced slice:
the least time the chip could take for the forward and backward passes
the slice ran, from the cell's static shapes (``ops_count``), over the
kernels' device time. The passes are counted by the ``flash_dkv`` calls,
one a layer a step."""

from benchmarks import ops_count


def read(ctx):
    trace = ctx.get("trace")
    if not trace or ctx["peaks"] is None:
        return None
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if "flash_" in name)
    calls = sum(n for name, n in trace["op_counts"].items()
                if "flash_dkv" in name)
    if not seconds or not calls:
        return None
    shape = (ctx["batch"], ctx["num_heads"], ctx["seq_len"],
             ctx["hidden_size"] // ctx["num_heads"])
    flops = calls * sum(ops_count.flash_causal_flops(*shape))
    nbytes = calls * sum(ops_count.flash_bytes(*shape))
    return ops_count.roofline_percent(flops, nbytes, seconds,
                                      ctx["peaks"])[0]
