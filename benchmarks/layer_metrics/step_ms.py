"""Host clock over train steps that are each waited for
(``block_until_ready``), the last quarter of a traced window, taken over
all of them together."""


def read(ctx):
    n = ctx.get("blocked_steps")
    return 1e3 * ctx["blocked_s"] / n if n else None
