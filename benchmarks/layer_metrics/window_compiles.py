"""XLA backend compiles inside the window (``xla.compile.count``, the
program's ``jax.monitoring`` listener; a persistent-cache load counts
too). A clean window reads 0."""


def read(ctx):
    return ctx["counters"].get("xla.compile.count", 0)
