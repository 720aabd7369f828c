"""Share of the traced slice in which chip 0 was idle while the
engine's thread was in any other program phase: the scheduler's bookkeeping, waiting for the lock, having no work
(``span_reduce``: gaps split by overlap among the innermost program
phases). The four ``idle_*`` shares add up to ``device_idle_share``."""

from benchmarks import span_reduce


def read(ctx):
    return span_reduce.idle_share(ctx, "host")
