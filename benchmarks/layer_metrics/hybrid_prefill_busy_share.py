"""Share of chip 0's busy time in the traced slice that a hybrid
(state-space and attention) model's prefill programs took: the ``XLA
Modules`` events ``jit_jamba_paged_prefill*`` and
``jit_jamba_paged_extend*`` over busy time (``span_reduce``). None where
no such program ran (a program without the model)."""

from benchmarks import span_reduce

_PREFILL = ("jit_jamba_paged_prefill", "jit_jamba_paged_extend")


def read(ctx):
    spans = span_reduce.of_cell(ctx)
    if not spans or not any(m.startswith("jit_jamba_")
                            for m in spans["busy_by_module"]):
        return None
    prefill = sum(s for m, s in spans["busy_by_module"].items()
                  if m.startswith(_PREFILL))
    return 100.0 * prefill / spans["busy_s"]
