"""Share of chip 0's busy time in the traced slice that the prefill
programs took: the ``XLA Modules`` events ``jit_llama_paged_prefill*``
and ``jit_llama_paged_extend*`` over busy time (``span_reduce``). The
eager pool writes are programs of their own and not in it. None where
no serving program carries its name (a program from before the names)."""

from benchmarks import span_reduce

_PREFILL = ("jit_llama_paged_prefill", "jit_llama_paged_extend")


def read(ctx):
    spans = span_reduce.of_cell(ctx)
    if not spans or not any(m.startswith("jit_llama_paged_")
                            for m in spans["busy_by_module"]):
        return None
    prefill = sum(s for m, s in spans["busy_by_module"].items()
                  if m.startswith(_PREFILL))
    return 100.0 * prefill / spans["busy_s"]
