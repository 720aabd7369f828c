"""Mixture-of-Experts with expert parallelism.

Parity: reference `python/paddle/incubate/distributed/models/moe/`
(MoELayer :263, MoEScatter/MoEGather PyLayers over global_scatter/
global_gather all-to-all collective ops, gates gshard/switch/naive,
capacity pruning kernels prune_gate_by_capacity/limit_by_capacity).

TPU-first (GShard formulation): routing is expressed as dense one-hot
dispatch/combine einsums over an expert axis; expert weights are stacked
[E, ...] and sharded over the `ep` mesh axis, so GSPMD partitions the
vmapped expert compute and inserts the all-to-alls the reference issues
manually via global_scatter/global_gather. Capacity pruning is the
position-in-expert cumsum mask — same semantics as limit_by_capacity.

Serving takes the other path (``DroplessMoE`` / ``dropless_moe``): no
capacity and no ``[T, E, C]`` tensor. Every (token, expert) assignment
becomes a row, the rows are sorted by expert and go through a grouped
matmul over the experts held (``kernels/pallas/moe_gmm.py``), so no
token is ever dropped and the cost follows the rows, not ``T x E``.
Training keeps the capacity path above.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Parameter, Tensor
from .api import shard_tensor
from .mesh import get_mesh
from ..profiler import metrics as _metrics
from .placement import Replicate, Shard

# route the grouped expert matmul took, counted where the layer is
# traced (one movement a compiled layer, as ``serving.kernel.pallas``)
_GMM_PALLAS = _metrics.counter("serving.kernel.moe_gmm.pallas")
_GMM_PLAIN = _metrics.counter("serving.kernel.moe_gmm.plain")

__all__ = ["MoELayer", "TopKGate", "DroplessMoE", "dropless_moe",
           "route_topk", "route_sigmoid_topk"]


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def topk_gating(logits, top_k, capacity, *, second_noise=0.0, key=None):
    """GShard-style top-k dispatch/combine.

    logits: [T, E] float32. Returns (dispatch [T,E,C] bool-ish,
    combine [T,E,C] float, aux_loss scalar).
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)

    gates = []
    masks = []
    p = probs
    for k in range(top_k):
        idx = jnp.argmax(p, axis=-1)
        mask = _one_hot(idx, E)
        gates.append(jnp.sum(probs * mask, axis=-1))  # [T]
        masks.append(mask)
        p = p * (1.0 - mask)

    # aux load-balance loss (GShard eq.4 / reference gshard_gate)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(masks[0], axis=0)
    aux = jnp.sum(me * ce) * E

    # position within each expert's queue, over all k choices in order
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    prev_counts = jnp.zeros((E,), jnp.float32)
    # top-1 = Switch semantics (raw router prob); top-k>1 = Mixtral/GShard
    # normalization over the chosen experts
    denom = sum(gates) if top_k > 1 else jnp.ones_like(gates[0])
    for mask, gate in zip(masks, gates):
        pos = jnp.cumsum(mask, axis=0) - 1.0 + prev_counts[None, :]
        prev_counts = prev_counts + jnp.sum(mask, axis=0)
        in_cap = (pos < capacity) & (mask > 0)
        pos_clamped = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
        sel = in_cap.astype(jnp.float32)  # [T, E]
        pos_oh = _one_hot(pos_clamped, capacity) * sel[..., None]
        dispatch = dispatch + mask[..., None] * pos_oh
        gate_norm = jnp.where(denom > 0, gate / jnp.maximum(denom, 1e-9),
                              0.0)
        combine = combine + (gate_norm[:, None, None] *
                             mask[..., None] * pos_oh)
    return dispatch, combine, aux


class TopKGate(nn.Layer):
    """Gate network (reference gate/gshard_gate.py, switch_gate.py: switch
    is top_k=1, gshard top_k=2)."""

    def __init__(self, d_model, num_experts, top_k=2,
                 capacity_factor=1.25):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.weight = self.create_parameter(
            shape=[d_model, num_experts],
            default_initializer=nn.initializer.XavierUniform())

    def capacity(self, num_tokens):
        return max(int(math.ceil(
            self.top_k * num_tokens / self.num_experts *
            self.capacity_factor)), 4)


class MoELayer(nn.Layer):
    """MoE layer (reference moe_layer.py:263 API: gate + experts +
    moe_group). ``experts``: list of identical Layers (e.g. LlamaMLP).
    aux loss is accumulated on ``self.aux_loss`` each forward (the
    reference returns it via gate state)."""

    def __init__(self, gate=None, experts=None, d_model=None,
                 num_experts=None, top_k=2, capacity_factor=1.25,
                 mesh=None, ep_axis=None, moe_group=None,
                 recompute_interval=0):
        super().__init__()
        if gate is None:
            gate = TopKGate(d_model, num_experts or len(experts),
                            top_k=top_k, capacity_factor=capacity_factor)
        self.gate = gate
        self._template = experts[0]
        self._n_experts = len(experts)
        self._mesh = mesh or get_mesh()
        self._ep_axis = ep_axis
        self.aux_loss = None

        names = [n for n, _ in experts[0].named_parameters()]
        self._expert_param_names = names
        self._stacked = nn.ParameterList()
        for name in names:
            arrs = [dict(e.named_parameters())[name]._data for e in experts]
            stacked = Parameter(jnp.stack(arrs, 0))
            stacked.name = "experts." + name
            if self._mesh is not None and ep_axis is not None and \
                    ep_axis in self._mesh.dim_names:
                placements = [Replicate()] * self._mesh.ndim
                placements[self._mesh.dim_names.index(ep_axis)] = Shard(0)
                shard_tensor(stacked, self._mesh, placements)
            self._stacked.append(stacked)

    def forward(self, x):
        E = self._n_experts
        top_k = self.gate.top_k
        template = self._template
        names = self._expert_param_names
        orig_shape = None

        T = 1
        for s in x.shape[:-1]:
            T *= s
        capacity = self.gate.capacity(T)

        def pure(xa, gate_w, *expert_params):
            shape = xa.shape
            tokens = xa.reshape(-1, shape[-1])  # [T, d]
            logits = (tokens.astype(jnp.float32) @
                      gate_w.astype(jnp.float32))
            dispatch, combine, aux = topk_gating(logits, top_k, capacity)
            # dispatch tokens: [E, C, d]
            expert_in = jnp.einsum("tec,td->ecd",
                                   dispatch.astype(xa.dtype), tokens)
            params = dict(zip(names, expert_params))

            def run_one(p_one, x_one):
                from .pipeline import _functional_call
                return _functional_call(template, p_one, x_one)

            expert_out = jax.vmap(run_one)(params, expert_in)  # [E, C, d']
            out = jnp.einsum("ecd,tec->td", expert_out,
                             combine.astype(expert_out.dtype))
            out = out.reshape(*shape[:-1], out.shape[-1]).astype(xa.dtype)
            return out, aux.astype(jnp.float32)

        out, aux = apply(pure, x, self.gate.weight, *list(self._stacked),
                         name="moe")
        self.aux_loss = aux
        return out


# ---------------------------------------------------------------------------
# dropless routing for serving: sort by expert + grouped matmul
# ---------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST


def gmm_route(kernel_mode=None):
    """Where the grouped matmul of a resolved ``FLAGS_paged_kernel`` mode
    runs on this backend: ``pallas`` (TPU), ``interpret`` (the same
    kernel interpreted, ``pallas`` forced on the CPU) or ``plain`` (the
    sorted ``ragged_dot``: the CPU's default and ``dense``)."""
    from ..inference.paged import kernel_route
    route = kernel_route(kernel_mode)
    return "plain" if route == "dense" else route


def _router_logits(x, router_w):
    """x [T, d] times the router [d, E] in float32 at ``highest``
    precision (the TPU's default would round the float32 operands to
    bfloat16)."""
    return jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                      precision=_HIGHEST)


def route_topk(x, router_w, top_k, norm_topk_prob=True):
    """The router: softmax over ALL experts in float32 (the product at
    ``highest`` precision: the TPU's default would round the float32
    operands to bfloat16), the ``top_k`` largest, renormalised over the
    chosen ones when ``norm_topk_prob``. x [T, d] -> (weights [T, k]
    float32, expert ids [T, k] int32)."""
    probs = jax.nn.softmax(_router_logits(x, router_w), axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx.astype(jnp.int32)


def route_sigmoid_topk(x, router_w, bias, top_k, norm_topk_prob=True,
                       scaling=1.0):
    """The ``noaux_tc`` router with one group: sigmoid scores over ALL
    experts in float32 (the product at ``highest`` precision, as
    :func:`route_topk`), the ``top_k`` largest of ``scores + bias`` (the
    correction ``bias`` [E] picks and never weighs), their own scores
    renormalised over the chosen ones when ``norm_topk_prob``, times
    ``scaling``. x [T, d] -> (weights [T, k] float32, expert ids [T, k]
    int32)."""
    scores = jax.nn.sigmoid(_router_logits(x, router_w))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * jnp.float32(scaling), idx.astype(jnp.int32)


def dropless_moe(x, router_w, w_gate, w_up, w_down, *, top_k,
                 norm_topk_prob=True, expert_lo=0, route="plain",
                 valid=None, kernel_tag="", router=None, shared=None):
    """The part of a SwiGLU expert layer's output that the experts
    ``[expert_lo, expert_lo + E_held)`` give, with nothing dropped.

    x [T, d]; ``router_w`` [d, E] routes over all E experts; ``w_gate``,
    ``w_up`` [E_held, d, f] and ``w_down`` [E_held, f, d] are the held
    experts, stacked. An assignment to an expert held elsewhere adds
    nothing here (its holder adds it: the parts of all holders sum to
    the whole layer). ``valid`` [T] bool marks the rows that are real
    (a batch's idle slots are not): the others are routed nowhere,
    counted nowhere and come out zero. Returns (y [T, d] in x's dtype,
    rows routed to each of the E experts [E] int32, the router's
    (weights [T, k] float32, expert ids [T, k])). ``route``:
    ``gmm_route``; ``kernel_tag`` ends the Pallas calls' names
    (``moe_gmm_swiglu<tag>``, ``moe_gmm<tag>``), so that a trace tells
    one program's calls from another's. ``router(x, router_w) ->
    (weights [T, k], expert ids [T, k])`` stands in for the softmax
    top-k of :func:`route_topk`; ``shared`` = (gate [d, f], up [d, f],
    down [f, d]) is an expert every row passes, unweighted, added where
    it is handed in (one holder of a split layer hands it in)."""
    from ..kernels.pallas import moe_gmm as K
    t, d = x.shape
    n_experts = router_w.shape[1]
    held = w_gate.shape[0]
    # the stages' names in a traced program (``.../pt.ffn/mlp/dispatch/
    # sort``): what a profile splits the layer's time by
    with jax.named_scope("router"):
        weights, idx = route_topk(x, router_w, top_k, norm_topk_prob) \
            if router is None else router(x, router_w)
    with jax.named_scope("dispatch"):
        flat = idx.reshape(-1)                          # [A], A = T * k
        a = flat.shape[0]
        real = jnp.ones((a,), bool) if valid is None \
            else jnp.repeat(valid, top_k)
        counts = jnp.zeros((n_experts,), jnp.int32).at[flat].add(
            real.astype(jnp.int32))
        local = flat - jnp.int32(expert_lo)
        mine = (local >= 0) & (local < held) & real
        # assignments held elsewhere sort past the last group
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)
        sorted_key = key[order]
        sizes = jax.lax.dynamic_slice(counts, (jnp.int32(expert_lo),),
                                      (held,))
        tm = K.tile_rows(a, held)
        m, offsets, padded, tile_expert, num_tiles = K.tile_layout(
            sizes, tm, a)
        starts = jnp.cumsum(sizes) - sizes
        safe = jnp.minimum(sorted_key, held - 1)
        rank = jnp.arange(a, dtype=jnp.int32) - starts[safe]
        # out of range for the rows held elsewhere: the scatter drops
        # them
        dest_sorted = jnp.where(sorted_key < held, offsets[safe] + rank, m)
        rows = jnp.zeros((m, d), x.dtype).at[dest_sorted].set(
            x[order // top_k], mode="drop")
    with jax.named_scope("experts"):
        if route == "plain":
            h = K.moe_gmm_swiglu_plain(rows, w_gate, w_up, padded)
            y = K.moe_gmm_plain(h, w_down, padded)
        else:
            interpret = route == "interpret"
            h = K.moe_gmm_swiglu(rows, w_gate, w_up, tile_expert,
                                 num_tiles, tm=tm, interpret=interpret,
                                 tag=kernel_tag)
            y = K.moe_gmm(h, w_down, tile_expert, num_tiles, tm=tm,
                          interpret=interpret, tag=kernel_tag)
    with jax.named_scope("combine"):
        dest = jnp.zeros((a,), jnp.int32).at[order].set(
            jnp.minimum(dest_sorted, m - 1))
        picked = jnp.where(mine[:, None], y[dest].astype(jnp.float32), 0.0)
        out = jnp.sum(picked.reshape(t, top_k, d) * weights[..., None],
                      axis=1)
    if shared is not None:
        with jax.named_scope("shared"):
            s_gate, s_up, s_down = shared
            f32 = jnp.float32
            h = jax.nn.silu(
                jnp.matmul(x, s_gate, preferred_element_type=f32)) \
                * jnp.matmul(x, s_up, preferred_element_type=f32)
            out = out + jnp.matmul(h.astype(x.dtype), s_down,
                                   preferred_element_type=f32)
    return out.astype(x.dtype), counts, (weights, idx)


class DroplessMoE(nn.Layer):
    """SwiGLU expert layer with dropless top-k routing (no capacity, no
    auxiliary loss): what a served sparse decoder runs. ``scoring``
    ``softmax`` routes by :func:`route_topk`; ``sigmoid`` by
    :func:`route_sigmoid_topk`, with a correction bias [E] of its own
    (``e_score_correction_bias``) and ``routed_scaling_factor``.
    ``shared_width`` > 0 adds one shared expert of that width that every
    row passes; where the layer is split by ``expert_range`` the holder
    of expert 0 computes it, so that the parts still sum to the layer.
    The experts' matrices are created stacked, ``[E_held, ...]``,
    one parameter a projection. ``expert_range=(lo, hi)`` is the range
    of experts this holder computes (all by default): the router still
    scores all ``num_experts``, which is what expert parallelism needs,
    and a forward gives this range's part of the layer's output.

    ``forward`` returns the Tensor; a ``counts_sink`` list is appended
    the rows routed to each expert ([E] int32), as ``kv_sink`` is the
    keys and values, and a ``route_sink`` list the router's (weights
    [T, k] float32, expert ids [T, k]) as this forward computed them."""

    def __init__(self, d_model, d_expert, num_experts, top_k,
                 norm_topk_prob=True, expert_range=None, weight_attr=None,
                 scoring="softmax", routed_scaling_factor=1.0,
                 shared_width=0, bias_attr=None):
        super().__init__()
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"DroplessMoE: scoring {scoring!r} is "
                             "neither 'softmax' nor 'sigmoid'")
        lo, hi = expert_range or (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"DroplessMoE: expert_range {(lo, hi)} is "
                             f"not within the {num_experts} experts")
        self.num_experts = num_experts
        self.top_k = top_k
        self.norm_topk_prob = norm_topk_prob
        self.expert_lo = lo
        held = hi - lo
        self.router = self.create_parameter(
            shape=[d_model, num_experts], attr=weight_attr)
        self.gate_proj = self.create_parameter(
            shape=[held, d_model, d_expert], attr=weight_attr)
        self.up_proj = self.create_parameter(
            shape=[held, d_model, d_expert], attr=weight_attr)
        self.down_proj = self.create_parameter(
            shape=[held, d_expert, d_model], attr=weight_attr)
        self.scoring = scoring
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.e_score_correction_bias = self.create_parameter(
            shape=[num_experts], attr=bias_attr, is_bias=True) \
            if scoring == "sigmoid" else None
        self.holds_shared = bool(shared_width) and lo == 0
        if self.holds_shared:
            self.shared_gate_proj = self.create_parameter(
                shape=[d_model, shared_width], attr=weight_attr)
            self.shared_up_proj = self.create_parameter(
                shape=[d_model, shared_width], attr=weight_attr)
            self.shared_down_proj = self.create_parameter(
                shape=[shared_width, d_model], attr=weight_attr)

    def forward(self, x, kernel_mode=None, counts_sink=None, valid=None,
                kernel_tag="", route_sink=None):
        route = gmm_route(kernel_mode)
        (_GMM_PLAIN if route == "plain" else _GMM_PALLAS).inc()

        # plain values in the closure: they are part of the dispatch
        # cache's key
        top_k, norm, lo = self.top_k, self.norm_topk_prob, self.expert_lo
        sigmoid, scaling = self.scoring == "sigmoid", \
            self.routed_scaling_factor
        shared, masked = self.holds_shared, valid is not None
        extras = ([valid] if masked else []) \
            + ([self.e_score_correction_bias] if sigmoid else []) \
            + ([self.shared_gate_proj, self.shared_up_proj,
                self.shared_down_proj] if shared else [])

        def pure(xa, router, wg, wu, wd, *rest):
            rest = list(rest)
            rows = rest.pop(0) if masked else None
            bias = rest.pop(0) if sigmoid else None
            y, counts, (weights, idx) = dropless_moe(
                xa.reshape(-1, xa.shape[-1]), router, wg, wu, wd,
                top_k=top_k, norm_topk_prob=norm, expert_lo=lo,
                route=route, valid=rows, kernel_tag=kernel_tag,
                router=(lambda m, rw: route_sigmoid_topk(
                    m, rw, bias, top_k, norm, scaling)) if sigmoid
                else None,
                shared=tuple(rest) if shared else None)
            return y.reshape(xa.shape), counts, weights, idx

        out, counts, weights, idx = apply(
            pure, x, self.router, self.gate_proj, self.up_proj,
            self.down_proj, *extras, name="dropless_moe")
        if counts_sink is not None:
            counts_sink.append(counts)
        if route_sink is not None:
            route_sink.append((weights, idx))
        return out
