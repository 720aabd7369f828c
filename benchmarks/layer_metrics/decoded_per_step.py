"""Tokens a scheduler step decodes: ``serving.decoded_tokens`` over
``serving.steps`` over the window. With every slot full it nears the
engine's slot count."""


def read(ctx):
    steps = ctx["counters"].get("serving.steps", 0)
    return ctx["counters"].get("serving.decoded_tokens", 0) / steps \
        if steps else None
