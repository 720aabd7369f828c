"""Mean time of a scheduler step (admission with its prefills, then one
decode step for every live slot): sum over count of ``serving.step_us``
over the window. The split into prefill and decode needs spans the
program does not have yet."""


def read(ctx):
    h = ctx["counters"].get("serving.step_us")
    return h["sum"] / h["count"] / 1e3 if h and h["count"] else None
