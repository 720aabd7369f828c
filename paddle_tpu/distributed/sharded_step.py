"""Sharded (hybrid-parallel) train step.

This is the load-bearing distributed runtime: the analogue of the
reference's entire hybrid-parallel engine (HybridParallelOptimizer +
EagerReducer allreduce overlap + sharding stages + Partitioner/Resharder,
SURVEY.md §2.3). One mesh, parameters placed by dist attrs, batch sharded
on the data axes — jit + GSPMD emit every collective (grad reductions
become reduce-scatters/all-reduces over ICI, resharded activations get
all-gathers) and overlap them with compute automatically.

ZeRO stages map to *optimizer-state placements* (reference
dygraph_sharding_optimizer.py:44 semantics):
- stage 1/2: slots sharded over the data axis, params replicated
- stage 3:   params themselves sharded over the data axis
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..jit import TrainStep
from .mesh import ProcessMesh
from .placement import Replicate, Shard, named_sharding


def _shard_like_param(arr, p, mesh, opt_axis=None):
    """Sharding for one optimizer slot array: same placements as the param
    when shapes match (+ optionally further sharded over ``opt_axis`` for
    ZeRO-1/2), replicated otherwise."""
    if p._dist_attr is None:
        return None
    pmesh, placements = p._dist_attr
    if arr.shape != p._data.shape:
        return named_sharding(pmesh, [Replicate()] * pmesh.ndim, arr.ndim)
    placements = list(placements)
    if opt_axis is not None:
        axis_idx = pmesh.dim_names.index(opt_axis)
        if placements[axis_idx].is_replicated():
            # shard the largest currently-unsharded dim over the opt axis
            taken = {pl.dim for pl in placements if pl.is_shard()}
            cand = [d for d in range(arr.ndim) if d not in taken and
                    arr.shape[d] % pmesh.get_dim_size(opt_axis) == 0]
            if cand:
                dim = max(cand, key=lambda d: arr.shape[d])
                placements[axis_idx] = Shard(dim)
    return named_sharding(pmesh, placements, arr.ndim)


class ShardedTrainStep(TrainStep):
    """TrainStep over a ProcessMesh.

    ``data_placements``: placements for every batch leaf (default:
    Shard(0) over the first mesh axis — pure DP on axis 0).
    ``shard_optimizer_axis``: mesh axis name to shard optimizer slots over
    (ZeRO stage 1/2); None keeps slots placed like their params.
    """

    def __init__(self, model, optimizer, step_fn=None, mesh=None,
                 data_placements=None, shard_optimizer_axis=None,
                 donate=True, offload=None):
        super().__init__(model, optimizer, step_fn, donate=donate)
        assert mesh is not None, "ShardedTrainStep requires a ProcessMesh"
        self._mesh = mesh
        if data_placements is None:
            data_placements = [Shard(0)] + \
                [Replicate()] * (mesh.ndim - 1)
        self._data_placements = data_placements
        self._opt_axis = shard_optimizer_axis
        self._slots_placed = set()
        # CPU offload (reference group_sharded_stage3.py:85 `offload`):
        # "os" parks optimizer slots in `pinned_host` memory between
        # steps; "os+params" parks the (ZeRO-3-sharded) params there too.
        # __call__ prefetches them onto their device shardings (async
        # device_put, overlapped with batch placement) and flushes the
        # updated state back after the step — the reference's hand-rolled
        # CUDA-stream prefetch/flush, expressed as memory-kind transfers.
        assert offload in (None, "os", "os+params"), offload
        self._offload = offload

    def _kernel_mesh(self):
        """Pallas kernels in the step run under shard_map over this mesh,
        the batch split as the data placements split dim 0."""
        from ..kernels.on_mesh import kernel_mesh
        batch_axes = [name for name, pl in zip(self._mesh.dim_names,
                                               self._data_placements)
                      if pl.is_shard(0)]
        return kernel_mesh(self._mesh.jax_mesh, batch_axes)

    def _out_shardings(self):
        """Pin updated params (and their slots) to their declared
        placements so a step never silently re-lays-out the model; loss /
        aux / buffers are left to XLA."""
        param_sh = []
        slot_sh = []
        for _, p in self._params:
            if p._dist_attr is None:
                param_sh.append(None)
                slot_sh.append(None)
                continue
            pmesh, placements = p._dist_attr
            param_sh.append(named_sharding(pmesh, placements, p.ndim))
            st = self._place_slots(p)
            slot_sh.append({
                nm: (None if arr is None else arr.sharding)
                for nm, arr in st.items()})
        return (None, None, param_sh, slot_sh, None)

    def _place_slots(self, p):
        """Device_put optimizer slots with their ZeRO placements once."""
        opt = self._opt
        st = opt._slots_for(p)
        if id(p) in self._slots_placed:
            return st
        for nm, arr in st.items():
            if arr is None:
                continue
            sh = _shard_like_param(arr, p, self._mesh, self._opt_axis)
            if sh is not None:
                if self._offload is not None:
                    sh = sh.with_memory_kind("pinned_host")
                st[nm] = jax.device_put(arr, sh)
        self._slots_placed.add(id(p))
        return st

    def _prefetch(self):
        """H2D: move offloaded slots (and params) onto their device
        shardings before dispatching the step. The device_puts are async —
        they overlap with the host-side batch placement below."""
        if self._offload is None:
            return
        opt = self._opt
        for _, p in self._params:
            if p._dist_attr is None:
                continue
            st = opt._slots_for(p)
            for nm, arr in st.items():
                if arr is None:
                    continue
                sh = _shard_like_param(arr, p, self._mesh, self._opt_axis)
                if sh is not None:
                    st[nm] = jax.device_put(arr, sh)
            if self._offload == "os+params":
                pmesh, placements = p._dist_attr
                p._rebind(jax.device_put(
                    p._data, named_sharding(pmesh, placements, p.ndim)))

    def _flush_to_host(self):
        """D2H: park the updated slots (and params) back in pinned host
        memory until the next step."""
        if self._offload is None:
            return
        opt = self._opt
        for _, p in self._params:
            if p._dist_attr is None:
                continue
            st = opt._state.get(id(p))
            if st:
                for nm, arr in st.items():
                    if arr is None or not hasattr(arr, "sharding"):
                        continue
                    st[nm] = jax.device_put(
                        arr, arr.sharding.with_memory_kind("pinned_host"))
            if self._offload == "os+params":
                p._rebind(jax.device_put(
                    p._data,
                    p._data.sharding.with_memory_kind("pinned_host")))

    def _place(self, batch):
        """Place params (idempotent: already committed), slots, and the
        batch on the mesh; returns the placed batch."""
        for _, p in self._params:
            if p._dist_attr is not None:
                self._place_slots(p)
        self._prefetch()
        placed = []
        for leaf in batch:
            t = leaf if isinstance(leaf, Tensor) else Tensor(leaf)
            sharding = named_sharding(self._mesh, self._data_placements,
                                      t.ndim)
            placed.append(Tensor(jax.device_put(t._data, sharding)))
        return placed

    def lower(self, *batch):
        placed = self._place(batch)
        with self._mesh.jax_mesh:
            return super().lower(*placed)

    def __call__(self, *batch):
        placed = self._place(batch)
        with self._mesh.jax_mesh:
            out = super().__call__(*placed)
        self._flush_to_host()
        return out
