"""Mean device time of one train step in the traced slice: the mean
duration of the ``jit_train_step`` events on chip 0's ``XLA Modules``
line that lie wholly inside the slice (``span_reduce``; the step running
as the trace starts or stops is cut to the slice's edge)."""

from benchmarks import span_reduce


def read(ctx):
    spans = span_reduce.of_cell(ctx)
    n, seconds = spans["whole_modules"].get("jit_train_step", (0, 0.0)) \
        if spans else (0, 0.0)
    return 1e3 * seconds / n if n else None
