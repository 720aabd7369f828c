"""Share of chip 0's busy time in the traced slice that operations under
no component took (``scope_reduce``): work the program has not named, or
copies and control flow that the compiler left without a path and that
nothing named consumes. None where no operation carries a component at
all (a program from before the scopes). With the six
``busy_in_*_share`` it adds up to 100."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, scope_reduce.UNSCOPED)
