"""Mean wait from submit to admission: sum over count of the scheduler's
``serving.queue_wait_us`` histogram over the window (both exact; its
buckets are too coarse for a tail)."""


def read(ctx):
    h = ctx["counters"].get("serving.queue_wait_us")
    return h["sum"] / h["count"] / 1e3 if h and h["count"] else None
