"""Rows the router gave an expert that got any, a layer of a block step:
``serving.moe.rows`` over ``serving.moe.experts_hit`` over the window
(both summed from the per-layer, per-expert counts the block step
returns with its tokens). The grouped matmul's group size: small groups
read a whole expert for little work. None where the program has no such
counters."""


def read(ctx):
    hit = ctx["counters"].get("serving.moe.experts_hit", 0)
    return ctx["counters"].get("serving.moe.rows", 0) / hit if hit else None
