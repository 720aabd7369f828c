"""Share of chip 0's busy time in the traced slice that a block-diffusion
model's prefill programs took: the ``XLA Modules`` events
``jit_sdar_paged_prefill*`` and ``jit_sdar_paged_extend*`` over busy time
(``span_reduce``). None where no such program ran (a program without the
model)."""

from benchmarks import span_reduce

_PREFILL = ("jit_sdar_paged_prefill", "jit_sdar_paged_extend")


def read(ctx):
    spans = span_reduce.of_cell(ctx)
    if not spans or not any(m.startswith("jit_sdar_")
                            for m in spans["busy_by_module"]):
        return None
    prefill = sum(s for m, s in spans["busy_by_module"].items()
                  if m.startswith(_PREFILL))
    return 100.0 * prefill / spans["busy_s"]
