"""Mean milliseconds a prefill spent writing its K and V into the pools
(one eager scatter a pool, two a layer): sum over count of
``serving.phase.prefill_pool_write_us`` over the window."""


def read(ctx):
    h = ctx["counters"].get("serving.phase.prefill_pool_write_us")
    return h["sum"] / h["count"] / 1e3 if h and h["count"] else None
