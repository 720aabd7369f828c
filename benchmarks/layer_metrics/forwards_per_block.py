"""Forwards a committed block cost: the slot-forwards of the window's
block steps, denoising and committing
(``serving.blockdiff.denoise_forwards`` + ``.commit_forwards``), over
``serving.blockdiff.blocks_committed``. A block that opens fully masked
takes the mix's ``denoise_steps`` and one commit; a first block the
prompt partly fills may take fewer. None where the program has no such
counters or committed no block."""


def read(ctx):
    counters = ctx["counters"]
    blocks = counters.get("serving.blockdiff.blocks_committed", 0)
    if not blocks:
        return None
    return (counters.get("serving.blockdiff.denoise_forwards", 0)
            + counters.get("serving.blockdiff.commit_forwards", 0)) / blocks
