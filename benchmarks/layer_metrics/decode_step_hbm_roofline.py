"""Share of the HBM bandwidth roofline a hybrid model's whole decode
step reached: the useful bytes of a step (``ops_count_ssm.
decode_step_bytes``: the weights once, the live slots' recurrent state
read and written, the context's K and V once) over the mean device time
of a ``jit_jamba_paged_decode`` event wholly inside the slice
(``decode_step_mfu.step_means``), over the chip's HBM bytes a second:
the bound that binds at 128 rows a step. Useful bytes only (no
activation, no padded page), so it cannot pass 100."""

import os

from benchmarks import harness, ops_count_ssm

_mfu = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "decode_step_mfu.py"))


def read(ctx):
    means = _mfu.step_means(ctx)
    if means is None:
        return None
    seconds, rows, context = means
    nbytes = ops_count_ssm.decode_step_bytes(ctx["cell"].config, rows,
                                             context)
    return 100.0 * nbytes / seconds / ctx["peaks"]["hbm_bytes_per_s"]
