"""Share of the HBM bandwidth roofline a latent-attention model's whole
decode step reached: the useful bytes of a step (``ops_count_mla.
decode_step_bytes``: the weights once, of the experts only those that
got a row, and the context's cached rows once a layer) over the mean
device time of a ``jit_xing_paged_decode`` event wholly inside the slice
(``mla_step_mfu.step_means``), over the chip's HBM bytes a second: the
bound that binds at 128 rows a step. Useful bytes only (no activation,
no padded lane or page), so it cannot pass 100."""

import os

from benchmarks import harness, ops_count_mla

_mfu = harness.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "mla_step_mfu.py"))


def read(ctx):
    import jax.numpy as jnp

    means = _mfu.step_means(ctx)
    if means is None:
        return None
    seconds, _, context, hit = means
    fields = ctx["cell"].config
    nbytes = ops_count_mla.decode_step_bytes(
        fields, context, hit, jnp.dtype(fields["torch_dtype"]).itemsize)
    return 100.0 * nbytes / seconds / ctx["peaks"]["hbm_bytes_per_s"]
