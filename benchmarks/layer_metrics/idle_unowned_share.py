"""Share of the traced slice in which chip 0 was idle and no program
phase of the engine's thread covered the gap (``span_reduce``). The four
``idle_*`` shares add up to ``device_idle_share``."""

from benchmarks import span_reduce


def read(ctx):
    return span_reduce.idle_share(ctx, span_reduce.UNOWNED)
