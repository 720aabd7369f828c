"""Share of the traced slice in which no operation ran on the device:
1 - union of the device-op intervals over the slice (``trace_reduce``)."""


def read(ctx):
    trace = ctx.get("trace")
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"]) \
        if trace else None
