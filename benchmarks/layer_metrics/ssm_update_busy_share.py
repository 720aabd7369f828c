"""Share of the device's busy time in the traced slice that the decode
step's state-update kernels took (``custom-call``s named ``ssm_update*``:
every live slot's recurrent state read and written once a state-space
layer)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernel = sum(s for name, s in trace["op_seconds"].items()
                 if "ssm_update" in name)
    return 100.0 * kernel / trace["busy_s"] if kernel else None
