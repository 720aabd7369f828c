"""Share of the HBM bandwidth roofline the decode step's state-update
kernel reached in the traced slice. The bytes a call (one state-space
layer) must move are every live slot's ``h`` read and written once and
the call's per-slot inputs and output (``ops_count_ssm.
state_update_bytes``), at the window's mean live slots a step
(``serving.ssm.state_slot_steps`` over the decode dispatches). Over the
mean device time of an ``ssm_update*`` call in the slice and the chip's
HBM bytes a second. The kernel steps all of a batch's slots, live or
not, so useful bytes are at most what it moves: it cannot pass 100."""

from benchmarks import ops_count_ssm


def read(ctx):
    trace, counters = ctx.get("trace"), ctx["counters"]
    steps = (counters.get("serving.phase.decode_dispatch_us")
             or {}).get("count", 0)
    slot_steps = counters.get("serving.ssm.state_slot_steps", 0)
    if not trace or not steps or not slot_steps or ctx["peaks"] is None:
        return None
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if "ssm_update" in name)
    calls = sum(n for name, n in trace["op_counts"].items()
                if "ssm_update" in name)
    if not seconds or not calls:
        return None
    nbytes = ops_count_ssm.state_update_bytes(ctx["cell"].config,
                                              slot_steps / steps)
    return 100.0 * nbytes / (seconds / calls) \
        / ctx["peaks"]["hbm_bytes_per_s"]
