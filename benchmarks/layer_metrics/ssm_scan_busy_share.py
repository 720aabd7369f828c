"""Share of the device's busy time in the traced slice that the
prefill's chunked selective-scan kernels took (``custom-call``s named
``ssm_scan*``)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel = sum(s for name, s in trace["op_seconds"].items()
                 if "ssm_scan" in name)
    return 100.0 * kernel / trace["busy_s"] if kernel else None
