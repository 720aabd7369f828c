"""Tape-based eager autograd engine.

Capability parity with the reference's eager autograd
(`paddle/fluid/eager/grad_node_info.h:197` GradNodeBase, `backward.cc:439`
egr::Backward), designed TPU-first: every recorded op stores the `jax.vjp`
pullback of its traced forward, so the backward pass is itself a chain of
XLA-compiled pullbacks (and the whole tape is re-traceable under `jax.jit`,
which is how the compiled train step fuses forward+backward+update into one
XLA program).
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax._src.source_info_util import (
    current_name_stack as _current_name_stack,
    set_name_stack as _set_name_stack)

from . import dtype as dtype_mod


class _GradState(threading.local):
    def __init__(self):
        self.enabled = True


_grad_state = _GradState()


def is_grad_enabled() -> bool:
    return _grad_state.enabled


def _set_grad_enabled(mode: bool) -> None:
    _grad_state.enabled = bool(mode)


class no_grad:
    """Context manager / decorator disabling gradient recording.

    Mirrors `paddle.no_grad` (reference: python/paddle/base/dygraph/base.py).
    """

    def __enter__(self):
        self._prev = _grad_state.enabled
        _grad_state.enabled = False
        return self

    def __exit__(self, *exc):
        _grad_state.enabled = self._prev
        return False

    def __call__(self, fn):
        def wrapper(*args, **kwargs):
            with no_grad():
                return fn(*args, **kwargs)

        wrapper.__name__ = getattr(fn, "__name__", "wrapped")
        return wrapper


class enable_grad:
    def __enter__(self):
        self._prev = _grad_state.enabled
        _grad_state.enabled = True
        return self

    def __exit__(self, *exc):
        _grad_state.enabled = self._prev
        return False


class Node:
    """One recorded op on the tape (analogue of a generated GradNode).

    ``vjp_fn`` maps a tuple of output cotangents (one per op output, in
    op-output order) to a tuple of input cotangents (one per entry of
    ``inputs``).

    ``parents`` snapshots each input's (producer node, out index) AT
    RECORD TIME — the eager analogue of the reference's TensorWrapper
    graph edges (paddle/fluid/eager/grad_node_info.h SetGradOutMeta):
    if an input tensor is later rebound by an in-place op, backward
    still routes cotangents through the graph as it stood when this op
    consumed the value, not through the mutation.
    """

    __slots__ = ("vjp_fn", "inputs", "parents", "out_meta", "name",
                 "fwd_fn", "tensor_vjp", "primals", "scope", "__weakref__")

    def __init__(
        self,
        vjp_fn: Callable,
        inputs: Sequence[Any],
        out_meta: Sequence[tuple],
        name: str = "",
        fwd_fn: Callable = None,
        tensor_vjp: Callable = None,
        primals: Sequence[Any] = None,
    ):
        self.vjp_fn = vjp_fn
        self.inputs = tuple(inputs)  # Tensors, vjp arg order
        self.parents = tuple((t._node, t._out_idx) for t in self.inputs)
        self.out_meta = tuple(out_meta)  # (shape, dtype) per op output
        self.name = name
        # Double-backward support (reference: GeneralGrad + composite VJP
        # rules, paddle/fluid/eager/backward.cc:439 + fluid/primitive/):
        # ``fwd_fn`` is the pure forward over the diff inputs — under
        # create_graph the backward is RE-RECORDED as the op
        # bwd(x..., ct...) = jax.vjp(fwd_fn, x...)[1](ct...), so
        # second-order paths flow through primals AND cotangents.
        # ``tensor_vjp`` (PyLayer) maps cotangent Tensors to grad Tensors
        # with recording enabled — differentiable if the user's backward is.
        self.fwd_fn = fwd_fn
        self.tensor_vjp = tensor_vjp
        # record-time diff-input ARRAYS (same order as ``inputs``): the
        # create_graph replay must recompute from the values this op
        # actually consumed, not the inputs' current (possibly in-place
        # rebound) arrays — the value analogue of the parent-edge
        # snapshot above. No extra memory: fwd_fn's closure already
        # references these arrays.
        self.primals = tuple(primals) if primals is not None else None
        # inside a traced program: the named scopes the op was recorded
        # under (``layers.3/pt.attn/self_attn/o_proj``), which the
        # backward re-enters so that the pullback's operations say which
        # part of the model they are the gradient of. Eager: None.
        stack = _current_name_stack()
        self.scope = stack if stack.stack else None

    def __repr__(self):
        return f"<Node {self.name} n_in={len(self.inputs)} n_out={len(self.out_meta)}>"


def _zero_cotangent(shape, dt):
    if dtype_mod.is_floating_point(dt) or dtype_mod.is_complex(dt):
        import jax.numpy as jnp

        return jnp.zeros(shape, dt)
    # Non-differentiable output: jax.vjp expects float0 cotangents.
    return np.zeros(shape, dtype=jax.dtypes.float0)


def _topo_order(root_nodes):
    """Reverse-topological order of reachable nodes (outputs before inputs)."""
    order = []
    state = {}  # node -> 0 visiting, 1 done
    stack = [(n, False) for n in root_nodes]
    while stack:
        node, processed = stack.pop()
        if processed:
            state[id(node)] = 1
            order.append(node)
            continue
        if id(node) in state:
            continue
        state[id(node)] = 0
        stack.append((node, True))
        for prod, _ in node.parents:
            if prod is not None and id(prod) not in state:
                stack.append((prod, False))
    order.reverse()  # produce consumers-first order
    return order


def backward(tensors, grad_tensors=None, retain_graph=False, _into=None,
             create_graph=False):
    """Run the tape backward from ``tensors``, accumulating into leaf ``.grad``.

    Mirrors `egr::Backward` (reference paddle/fluid/eager/backward.cc:439):
    seeds cotangents (ones for scalar roots), walks grad nodes in dependency
    order, accumulates gradients on leaf tensors. When ``_into`` is a dict,
    leaf gradients are collected there (id(tensor) -> array) instead of
    touching ``.grad`` — the functional `grad()` path.

    With ``create_graph=True`` the backward computation is itself recorded
    on the tape (cotangents are Tensors; every node's pullback is re-issued
    as a differentiable op), enabling grad-of-grad — the reference's
    GeneralGrad + composite-VJP capability (backward.cc:439,
    paddle/fluid/primitive/).
    """
    if create_graph:
        return _backward_create_graph(tensors, grad_tensors, _into)
    from .tensor import Tensor

    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif not isinstance(grad_tensors, (list, tuple)):
        grad_tensors = [grad_tensors]

    import jax.numpy as jnp

    # node id -> list of accumulated output cotangents (or None)
    pending: dict[int, list] = {}
    node_by_id: dict[int, Node] = {}
    leaf_grads: dict[int, Any] = {}
    leaf_by_id: dict[int, Tensor] = {}
    root_nodes = []

    def _seed(t, g):
        if t.stop_gradient:
            raise RuntimeError(
                "backward() called on a tensor with stop_gradient=True"
            )
        if g is None:
            if t._data.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {t.shape}"
                )
            g = jnp.ones(t._data.shape, t._data.dtype)
        else:
            g = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        _route(t, g)

    def _route(t, g):
        node = t._node
        if node is None:
            if not t.stop_gradient:
                key = id(t)
                leaf_by_id[key] = t
                leaf_grads[key] = g if key not in leaf_grads else leaf_grads[key] + g
            return
        nid = id(node)
        if nid not in pending:
            pending[nid] = [None] * len(node.out_meta)
            node_by_id[nid] = node
            root_nodes.append(node)
        slot = pending[nid]
        idx = t._out_idx
        slot[idx] = g if slot[idx] is None else slot[idx] + g

    for t, g in zip(tensors, grad_tensors):
        _seed(t, g)

    order = _topo_order(root_nodes)

    def _pull(node, cts):
        full = tuple(
            ct if ct is not None else _zero_cotangent(shape, dt)
            for ct, (shape, dt) in zip(cts, node.out_meta)
        )
        in_grads = node.vjp_fn(full)
        for t, (prod, idx), g in zip(node.inputs, node.parents, in_grads):
            if t.stop_gradient:
                continue
            if prod is None:
                key = id(t)
                leaf_by_id[key] = t
                leaf_grads[key] = (
                    g if key not in leaf_grads else leaf_grads[key] + g
                )
            else:
                pid = id(prod)
                if pid not in pending:
                    pending[pid] = [None] * len(prod.out_meta)
                    node_by_id[pid] = prod
                slot = pending[pid]
                slot[idx] = g if slot[idx] is None else slot[idx] + g

    for node in order:
        nid = id(node)
        cts = pending.get(nid)
        if cts is None:
            # Reachable from roots topologically but received no cotangent
            # (all consumers were grad-pruned); its inputs get zeros — skip.
            continue
        if node.scope is None:
            _pull(node, cts)
        else:  # a traced program: under the scopes of the node's forward
            with _set_name_stack(node.scope):
                _pull(node, cts)
        pending[nid] = None  # free cotangents early

    # Accumulate into .grad (GradNodeAccumulation analogue), or into the
    # caller's store for the functional grad() path.
    if _into is not None:
        for key, g in leaf_grads.items():
            _into[key] = g if key not in _into else _into[key] + g
    else:
        for key, g in leaf_grads.items():
            t = leaf_by_id[key]
            if t.grad is None:
                t.grad = Tensor(g, stop_gradient=True)
            else:
                t.grad = Tensor(t.grad._data + g, stop_gradient=True)

    if not retain_graph:
        for t in tensors:
            _release_graph(t)


def _node_grad_op(node, ct_tensors, float_idx):
    """Issue one node's backward as a recorded, differentiable op.

    ``ct_tensors``: cotangent Tensors for the node's FLOAT outputs (in
    ``float_idx`` order). Returns one grad Tensor (or None) per
    ``node.inputs`` entry.
    """
    from .tensor import Tensor
    from .dispatch import apply

    if node.tensor_vjp is not None:  # PyLayer: user backward on Tensors
        full_cts = []
        fi = 0
        for i, (shape, dt) in enumerate(node.out_meta):
            if i in float_idx:
                full_cts.append(ct_tensors[fi])
                fi += 1
            else:  # non-float output: zero cotangent placeholder
                full_cts.append(Tensor(np.zeros(shape, dt),
                                       stop_gradient=True))
        with enable_grad():
            grads = node.tensor_vjp(full_cts)
        out = []
        gi = iter(grads)
        for _t in node.inputs:
            g = next(gi, None)
            out.append(g if (g is None or isinstance(g, Tensor))
                       else Tensor(g))
        return out

    if node.fwd_fn is None:
        # legacy/special node (e.g. fused pipeline loss): backward runs on
        # arrays; grad-of-grad truncates here by construction
        full = tuple(
            (ct_tensors[float_idx.index(i)]._data
             if i in float_idx else
             _zero_cotangent(shape, dt))
            for i, (shape, dt) in enumerate(node.out_meta))
        arrs = node.vjp_fn(full)
        return [None if a is None else Tensor(a, stop_gradient=True)
                for a in arrs]

    n_in = len(node.inputs)
    fwd = node.fwd_fn
    out_meta = node.out_meta
    float_set = frozenset(float_idx)

    def bwd_fn(*vals):
        xs = vals[:n_in]
        ctf = vals[n_in:]
        _, vjp = jax.vjp(fwd, *xs)
        full, fi = [], 0
        for i, (shape, dt) in enumerate(out_meta):
            if i in float_set:
                full.append(ctf[fi])
                fi += 1
            else:
                full.append(np.zeros(shape, dtype=jax.dtypes.float0))
        return tuple(vjp(tuple(full)))

    # Replay from the RECORD-TIME primal values (node.primals), not the
    # inputs' current arrays — an in-place rebind between forward and
    # this backward must not change gradients. Shell tensors carry the
    # snapshot values; their graph edges are re-pointed below.
    from .tensor import Tensor as _T
    if node.primals is not None:
        shells = []
        for t, arr in zip(node.inputs, node.primals):
            s = _T(arr, stop_gradient=t.stop_gradient)
            shells.append(s)
    else:  # legacy node without a snapshot: current values
        shells = list(node.inputs)

    with enable_grad():
        outs = apply(bwd_fn, *shells, *ct_tensors,
                     name=(node.name or "op") + "_grad")
    outs = outs if isinstance(outs, list) else [outs]
    # The new node snapshots (producer, out_idx) of the shells (None —
    # they are leaves); re-route to the record-time snapshot so the
    # second-order paths thread through the original graph.
    new_node = next((o._node for o in outs
                     if getattr(o, "_node", None) is not None), None)
    if new_node is not None:
        by_id = {id(s): (t, p) for s, t, p in
                 zip(shells, node.inputs, node.parents)}
        new_parents = []
        new_inputs = []
        for t, p in zip(new_node.inputs, new_node.parents):
            orig = by_id.get(id(t))
            if orig is None:
                new_inputs.append(t)
                new_parents.append(p)
            else:
                # swap the shell back to the ORIGINAL tensor: a later
                # backward walk keys leaf accumulation by input object
                # identity, so grads must credit the real leaf, not the
                # shell. Values stay record-time: apply() snapshotted
                # the shell arrays into this node's own primals.
                new_inputs.append(orig[0])
                new_parents.append(orig[1])
        new_node.inputs = tuple(new_inputs)
        new_node.parents = tuple(new_parents)
    return outs


def _backward_create_graph(tensors, grad_tensors, _into):
    """The ``create_graph=True`` tape walk: cotangents are Tensors and each
    pullback is re-recorded, so the produced gradients carry their own
    differentiable graph."""
    from .tensor import Tensor
    import jax.numpy as jnp

    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif not isinstance(grad_tensors, (list, tuple)):
        grad_tensors = [grad_tensors]

    pending: dict[int, list] = {}
    leaf_grads: dict[int, Any] = {}
    leaf_by_id: dict[int, Tensor] = {}
    root_nodes = []

    def _route(t, g):
        node = t._node
        if node is None:
            if not t.stop_gradient:
                key = id(t)
                leaf_by_id[key] = t
                leaf_grads[key] = g if key not in leaf_grads \
                    else leaf_grads[key] + g
            return
        nid = id(node)
        if nid not in pending:
            pending[nid] = [None] * len(node.out_meta)
            root_nodes.append(node)
        slot = pending[nid]
        idx = t._out_idx
        slot[idx] = g if slot[idx] is None else slot[idx] + g

    with enable_grad():
        for t, g in zip(tensors, grad_tensors):
            if t.stop_gradient:
                raise RuntimeError(
                    "backward() called on a tensor with stop_gradient=True")
            if g is None:
                if t._data.size != 1:
                    raise RuntimeError(
                        "grad can be implicitly created only for scalar "
                        f"outputs; got shape {t.shape}")
                g = Tensor(jnp.ones(t._data.shape, t._data.dtype))
            elif not isinstance(g, Tensor):
                g = Tensor(jnp.asarray(g))
            _route(t, g)

        order = _topo_order(root_nodes)

        for node in order:
            nid = id(node)
            cts = pending.get(nid)
            if cts is None:
                continue
            float_idx = [
                i for i, (shape, dt) in enumerate(node.out_meta)
                if dtype_mod.is_floating_point(dt)
                or dtype_mod.is_complex(dt)]
            ct_tensors = []
            for i in float_idx:
                ct = cts[i]
                if ct is None:
                    shape, dt = node.out_meta[i]
                    ct = Tensor(jnp.zeros(shape, dt))
                ct_tensors.append(ct)
            in_grads = _node_grad_op(node, ct_tensors, float_idx)
            for t, (prod, idx), g in zip(node.inputs, node.parents,
                                         in_grads):
                if t.stop_gradient or g is None:
                    continue
                if prod is None:
                    key = id(t)
                    leaf_by_id[key] = t
                    leaf_grads[key] = g if key not in leaf_grads \
                        else leaf_grads[key] + g
                else:
                    pid = id(prod)
                    if pid not in pending:
                        pending[pid] = [None] * len(prod.out_meta)
                    slot = pending[pid]
                    slot[idx] = g if slot[idx] is None else slot[idx] + g
            pending[nid] = None

    if _into is not None:
        for key, g in leaf_grads.items():
            _into[key] = g if key not in _into else _into[key] + g
    else:
        with enable_grad():
            for key, g in leaf_grads.items():
                t = leaf_by_id[key]
                # accumulate as a RECORDED add: .grad must keep its tape
                # (a detached sum would silently break a later
                # grad(leaf.grad, ...) in the accumulation case)
                t.grad = g if t.grad is None else t.grad + g
    # create_graph implies the graph stays alive: the grad graph's parents
    # thread through the original nodes.


def _release_graph(root):
    """Drop tape references so intermediate activations can be freed."""
    node = root._node
    if node is None:
        return
    seen = set()
    stack = [node]
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        for prod, _ in n.parents:
            if prod is not None:
                stack.append(prod)
        n.vjp_fn = _dead_vjp
        n.inputs = ()
        n.parents = ()
        n.fwd_fn = None
        n.tensor_vjp = None
        n.primals = None


def _dead_vjp(*_):
    raise RuntimeError(
        "trying to backward through a graph a second time; "
        "pass retain_graph=True to backward()"
    )


def grad(
    outputs,
    inputs,
    grad_outputs=None,
    retain_graph=None,
    create_graph=False,
    allow_unused=False,
):
    """Functional gradient: d(outputs)/d(inputs) without touching ``.grad``.

    Mirrors `paddle.grad` (reference python/paddle/autograd/__init__.py).
    With ``create_graph=True`` the returned gradients carry their own tape
    and can be differentiated again (grad-of-grad / gradient penalties).
    """
    from .tensor import Tensor

    single = isinstance(inputs, Tensor)
    inputs = [inputs] if single else list(inputs)
    outputs = [outputs] if isinstance(outputs, Tensor) else list(outputs)

    store: dict[int, Any] = {}
    backward(outputs, grad_tensors=grad_outputs, retain_graph=True,
             _into=store, create_graph=create_graph)
    results = []
    for t in inputs:
        g = store.get(id(t))
        if g is None:
            if not allow_unused:
                raise RuntimeError(
                    "one of the input tensors received no gradient; "
                    "pass allow_unused=True to return None for it"
                )
            results.append(None)
        elif isinstance(g, Tensor):
            results.append(g)  # create_graph path: keeps its tape
        else:
            results.append(Tensor(g, stop_gradient=True))
    if not create_graph and (retain_graph is False or retain_graph is None):
        # create_graph keeps the graph alive: the grad graph's parent
        # edges thread through the original forward nodes
        for t in outputs:
            _release_graph(t)
    return results[0] if single else results
