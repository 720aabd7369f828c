"""Peak share of the KV pool's usable blocks that live requests pinned
(``PagedKVCache.occupancy()``: active over usable), sampled from the
client thread every 50 ms of the window. Memory reserved and unused is
what caps the batch."""


def read(ctx):
    samples = ctx.get("kv_active_share")
    return 100.0 * max(samples) if samples else None
