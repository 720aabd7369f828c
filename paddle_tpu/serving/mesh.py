"""Mesh-sharded serving: the ``(data, model)`` device mesh behind
``FLAGS_serving_mesh``.

Everything serving-side up to PR 14 ran on exactly one chip. This
module points the training-side mesh machinery (``distributed/mesh``,
``jax.sharding``) at inference:

- the **model axis** tensor-parallels the served Llama: attention
  q/k/v projections and MLP gate/up shard their OUTPUT dim
  (column-parallel — heads split contiguously across shards), o/down
  shard their INPUT dim (row-parallel — XLA inserts the psum at the
  projection boundary), and the paged KV block pools shard by
  **kv-head** along the same axis, so the attention gather + einsum is
  embarrassingly parallel over heads (no collective inside attention;
  the all_gather/psum_scatter pair lives at the projection
  boundaries). The decode attention runs under an explicit
  ``jax.shard_map`` so each shard routes its local pool through
  ``kernels/pallas/paged_attention.py``; everything else is expressed
  through ``NamedSharding`` on the program inputs and GSPMD
  propagation.
- the **data axis** partitions the scheduler's capacity into
  *slices*: decode slots and pool blocks are divided across
  ``data`` slices, new requests bind to the least-loaded slice, and
  ``PagedKVCache.occupancy()`` / the admission+shed watermarks report
  and read per-slice (the foundation for disaggregated
  prefill/decode and per-slice routing later).

Host-side block tables, refcounts, prefix-cache digests, COW and LRU
eviction are **untouched**: tables stay replicated numpy, so every
shard sees the same block ids and the sharded gather is just the
single-device gather on a narrower head axis. Greedy outputs are
bit-identical to the 1-device run wherever XLA reduction order allows
(tools/mesh_gate.py pins the corpus), and ``FLAGS_serving_mesh`` unset
/ ``1x1`` is byte-for-byte pre-mesh behavior with ``serving.mesh.*``
counter silence.
"""

from __future__ import annotations

import numpy as np

from ..core import flags as flags_mod
from ..distributed.mesh import MeshAxisError, validate_mesh_axes
from ..profiler import metrics as _metrics

__all__ = ["ServingMesh", "parse_mesh_spec", "resolve_serving_mesh",
           "MeshAxisError"]

# armed-only telemetry: all silent while FLAGS_serving_mesh is unset
# (tools/mesh_gate.py pins the silence)
_g_devices = _metrics.gauge("serving.mesh.devices")
_g_data = _metrics.gauge("serving.mesh.data_slices")
_g_model = _metrics.gauge("serving.mesh.model_shards")
_c_engines = _metrics.counter("serving.mesh.engines")

# param-name suffix -> partition kind along the model axis (the
# Megatron split Llama.tp_placement_rules documents for training,
# applied to the serving replica): column-parallel shards [in, out] on
# out, row-parallel on in; everything else (embeddings, norms, lm_head)
# stays replicated so vocab argmax needs no cross-shard reduction.
_COL_SUFFIXES = ("q_proj.weight", "k_proj.weight", "v_proj.weight",
                 "gate_proj.weight", "up_proj.weight")
_ROW_SUFFIXES = ("o_proj.weight", "down_proj.weight")


def parse_mesh_spec(spec):
    """``'DATAxMODEL'`` -> ``(data, model)`` ints. ``''``/``None``/
    falsy strings parse to ``(1, 1)`` (disarmed). Raises ValueError on
    anything else malformed."""
    s = str(spec or "").strip().lower()
    if s in ("", "0", "off", "none", "false"):
        return (1, 1)
    parts = s.split("x")
    if len(parts) != 2:
        raise ValueError(
            f"FLAGS_serving_mesh: expected 'DATAxMODEL' (e.g. '1x8'), "
            f"got {spec!r}")
    try:
        d, m = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(
            f"FLAGS_serving_mesh: non-integer axis in {spec!r}") from None
    if d < 1 or m < 1:
        raise ValueError(
            f"FLAGS_serving_mesh: axis sizes must be >= 1, got {spec!r}")
    return (d, m)


class ServingMesh:
    """One serving engine's ``(data, model)`` mesh + its sharding
    vocabulary. Construction validates the axes against the visible
    device count (``distributed.mesh.validate_mesh_axes`` — a
    structured :class:`MeshAxisError` naming the axis, never a deep
    jax failure)."""

    AXES = ("data", "model")

    def __init__(self, data, model):
        import jax
        from jax.sharding import Mesh

        self.data = int(data)
        self.model = int(model)
        validate_mesh_axes((self.data, self.model), self.AXES)
        n = self.data * self.model
        devices = np.array(jax.devices()[:n], dtype=object).reshape(
            self.data, self.model)
        self.jax_mesh = Mesh(devices, axis_names=self.AXES)

    # -- identity ------------------------------------------------------

    @property
    def spec(self):
        return f"{self.data}x{self.model}"

    @property
    def devices(self):
        return self.data * self.model

    @property
    def trivial(self):
        return self.devices == 1

    def __repr__(self):
        return f"ServingMesh({self.spec})"

    # -- sharding vocabulary -------------------------------------------

    def sharding(self, *parts):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.jax_mesh, PartitionSpec(*parts))

    @property
    def replicated(self):
        return self.sharding()

    def param_sharding(self, name):
        """NamedSharding for one model parameter by its qualified name
        (the ``named_parameters`` path): attention/MLP projections
        shard along ``model``, everything else replicates."""
        if name.endswith(_COL_SUFFIXES):
            return self.sharding(None, "model")
        if name.endswith(_ROW_SUFFIXES):
            return self.sharding("model", None)
        return self.replicated

    def kv_pool_sharding(self):
        """[num_blocks, block_size, Hk, D] pools shard by kv-head."""
        return self.sharding(None, None, "model", None)

    def kv_scale_sharding(self):
        """[num_blocks, block_size, Hk] int8 scale rows follow the
        pools' kv-head split."""
        return self.sharding(None, None, "model")

    # -- model compatibility -------------------------------------------

    def validate_model(self, config):
        """The model axis must divide every dim it splits: q heads,
        kv heads, and the MLP hidden dim. Raises :class:`MeshAxisError`
        naming the axis and the offending extent."""
        m = self.model
        if m == 1:
            return
        for what, extent in (("num_heads", config.num_heads),
                             ("num_kv_heads", config.num_kv_heads),
                             ("intermediate_size",
                              config.intermediate_size)):
            if extent % m != 0:
                raise MeshAxisError(
                    f"serving mesh model axis {m} does not divide "
                    f"{what}={extent} — choose a model axis that "
                    f"divides the head and hidden extents",
                    axis="model", size=m, device_count=self.devices)

    @property
    def shard_map_armed(self):
        """True when the decode attention runs under an explicit
        ``jax.shard_map``: whenever the model axis splits anything."""
        return self.model > 1


def resolve_serving_mesh(mesh=None):
    """Resolve a Scheduler's ``mesh`` ctor kwarg (the
    ``FLAGS_serving_prefix_cache`` read-once-at-construction
    convention): ``None`` reads ``FLAGS_serving_mesh``; a string
    parses as ``'DATAxMODEL'``; a :class:`ServingMesh` passes through.
    Returns ``None`` for the trivial ``1x1`` mesh — the disarmed,
    byte-for-byte pre-mesh path."""
    if mesh is None:
        mesh = str(flags_mod.flag("FLAGS_serving_mesh"))
    if isinstance(mesh, ServingMesh):
        return None if mesh.trivial else mesh
    d, m = parse_mesh_spec(mesh)
    if (d, m) == (1, 1):
        return None
    return ServingMesh(d, m)


def note_engine(mesh):
    """Armed-engine telemetry (Scheduler construction): mesh-shape
    gauges + the engines counter. Never called disarmed — the
    counter-silence contract."""
    _g_devices.set(mesh.devices)
    _g_data.set(mesh.data)
    _g_model.set(mesh.model)
    _c_engines.inc()
