"""Peak bytes in use on the fullest chip, read after the window and its
drain and before the reference runs
(``memory_stats()["peak_bytes_in_use"]``; a peak of the whole process,
which runs one cell)."""


def read(ctx):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e9 if peak else None
