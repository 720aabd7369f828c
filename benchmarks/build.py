"""Seeded weights, made on the device in one jitted call.

The model's own constructor runs inside ``jax.jit`` with the program's
RNG state swapped for a traced key (``core.random.scoped_key``, what the
compiled train step uses), so every parameter is drawn, scaled and cast
to its served type by one XLA program whose only input is the key: no
leaf-by-leaf dispatch, no float32 copy left on the device, and one
program for every seed.
"""

from __future__ import annotations

import numpy as np


def build_model(cls, config, dtype, seed):
    """``cls(config)`` with all parameters in ``dtype``, a pure function
    of ``seed`` (any non-negative int below 2**64)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.core import random as prandom

    made = []

    def make(key):
        prev = paddle.get_default_dtype()
        paddle.set_default_dtype(dtype)
        try:
            with prandom.scoped_key(key):
                model = cls(config)
        finally:
            paddle.set_default_dtype(prev)
        # layers that pin float32 (embedding, norm weights) follow
        model.to(dtype=dtype)
        made.append(model)
        return tuple(p._data for _, p in model.named_parameters())

    # the generator makes its key on first use; inside the trace that
    # would leave a tracer behind as the process's RNG state
    prandom.default_generator().get_state()
    seed = int(seed)
    key = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    arrays = jax.jit(make)(key)
    model = made[0]
    for (_, p), arr in zip(model.named_parameters(), arrays):
        p._data = arr  # the constructor left its tracer here
    return model
