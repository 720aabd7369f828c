"""Share of the device's busy time in the traced slice that the grouped
expert matmuls took (``custom-call``s named ``moe_gmm*``: gate/up and
down, of the block steps and of the prefills)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel = sum(s for name, s in trace["op_seconds"].items()
                 if "moe_gmm" in name)
    return 100.0 * kernel / trace["busy_s"] if kernel else None
