"""Share of the device's busy time in the traced slice that the absorbed
latent-attention decode kernel took (``custom-call``s named
``mla_decode*``)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel = sum(s for name, s in trace["op_seconds"].items()
                 if "mla_decode" in name)
    return 100.0 * kernel / trace["busy_s"] if kernel else None
