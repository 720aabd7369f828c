"""How a Pallas kernel call maps onto a device mesh.

A Mosaic kernel is a per-device program: XLA's SPMD partitioner cannot
split it, and libtpu registers no ``custom_partitioning`` callbacks, so on
real chips a kernel call inside a sharded program has to sit in an
explicit ``jax.shard_map``. The shardings of intermediates are not
visible while tracing, so the runtime that owns the mesh says how the
batch maps to it — ``ShardedTrainStep`` from its data placements, the
served Llama from its ``ServingMesh`` — with :func:`kernel_mesh`, and
the kernel entry points derive their specs from that with
:func:`batch_head_axes`: the batch over the declared batch axes, the
heads over every other axis that divides them. Attention is
embarrassingly parallel over batch x heads, so any such split is
correct; XLA reshards operands that arrive laid out differently.

Inside a ``shard_map`` that is already manual over the whole mesh (the
pipeline engine, the serving mesh's decode attention) a kernel call is
local and needs no wrap: :func:`current` returns None there.
"""

from __future__ import annotations

import contextlib
import math
import threading

import jax

__all__ = ["kernel_mesh", "current", "batch_head_axes"]

_state = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh, batch_axes=()):
    """Declare, for the code traced inside, the ``jax.sharding.Mesh`` the
    program is sharded over and the mesh axes its batch dim is split
    across. ``mesh=None`` (an unsharded model) declares nothing."""
    if mesh is None:
        yield
        return
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, tuple(batch_axes))
    try:
        yield
    finally:
        _state.ctx = prev


def current():
    """The declared ``(mesh, batch_axes)``, or None when nothing is
    declared or the trace is already manual over that whole mesh. Read
    it OUTSIDE any closure handed to ``core.dispatch.apply``: what a
    closure reads inside is invisible to the dispatch-cache key."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return None
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    if not manual:
        return ctx
    if manual >= set(ctx[0].axis_names):
        return None
    raise NotImplementedError(
        f"Pallas kernel call inside a shard_map that is manual over "
        f"{sorted(manual)} only, of mesh axes {ctx[0].axis_names}: make "
        f"the region manual over the whole mesh")


def batch_head_axes(ctx, batch, heads):
    """(batch axes, head axes) for a kernel whose operands carry ``batch``
    rows and ``heads`` KV heads: the declared batch axes where they
    divide the batch, and, in mesh order, every other axis that still
    divides the heads. None stands for "replicated"."""
    mesh, batch_axes = ctx
    size = mesh.shape
    if batch % math.prod(size[a] for a in batch_axes):
        batch_axes = ()
    head_axes = []
    for a in mesh.axis_names:
        if a not in batch_axes and \
                heads % (math.prod(size[h] for h in head_axes) * size[a]) == 0:
            head_axes.append(a)
    return tuple(batch_axes) or None, tuple(head_axes) or None
