"""Share of the device's busy time in the traced slice that the block
attention took (``custom-call``s named ``paged_block*``: the paged
kernels with a block's rows folded into the GQA group, which
``paged_attn_busy_share`` does not match)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel = sum(s for name, s in trace["op_seconds"].items()
                 if "paged_block" in name)
    return 100.0 * kernel / trace["busy_s"] if kernel else None
