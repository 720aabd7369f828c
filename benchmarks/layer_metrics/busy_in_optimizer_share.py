"""Share of chip 0's busy time in the traced slice that the operations
under the component ``optimizer`` took (``scope_reduce``: self time by the
innermost ``pt.<component>`` of an operation's path). The six
``busy_in_*_share`` and ``busy_unscoped_share`` add up to 100."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "optimizer")
