"""Share of the traced slice in which chip 0 was idle while the
engine's thread was in ``serving.decode.dispatch`` or ``serving.decode.readback``
(``span_reduce``: gaps split by overlap among the innermost program
phases). The four ``idle_*`` shares add up to ``device_idle_share``."""

from benchmarks import span_reduce


def read(ctx):
    return span_reduce.idle_share(ctx, "decode")
