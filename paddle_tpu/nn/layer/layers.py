"""Layer: the module system.

Parity target: reference `python/paddle/nn/layer/layers.py` (class Layer —
parameters/sublayers registries, hooks, state_dict, train/eval, to/astype).
"""

from __future__ import annotations

import collections
from typing import Callable, Iterator

import jax
import numpy as np
from jax._src.core import trace_state_clean as _trace_state_clean

from ...core import dtype as dtype_mod
from ...core.tensor import Parameter, Tensor


class HookRemoveHelper:
    def __init__(self, hooks: dict, hook_id: int):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtype_mod.convert_dtype(dtype) if dtype else None
        self._parameters = collections.OrderedDict()
        self._sub_layers = collections.OrderedDict()
        self._buffers = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._hook_id = 0
        # the name this layer is registered under in its parent (none
        # for a root): the segment ``__call__`` adds to the path of the
        # operations traced inside it
        self._name_scope = name_scope

    # -- registration ------------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError(
                    "call super().__init__() before assigning parameters")
            if name not in params:  # in-place keeps OrderedDict position
                _strip(self, name)
            params[name] = value
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError(
                    "call super().__init__() before assigning sublayers")
            # replacing an existing child (e.g. QAT swapping a Conv2D for
            # its fake-quant form inside a Sequential) must keep its
            # POSITION — strip+reinsert would move it to the end and
            # scramble the container's forward order
            if name not in layers:
                _strip(self, name)
            layers[name] = value
            value._named(name)
        elif params is not None and name in params:
            if value is None:
                params.pop(name)
                object.__setattr__(self, name, None)
            elif isinstance(value, Tensor):
                params[name].set_value(value)
            else:
                raise TypeError(
                    f"cannot assign {type(value)} to parameter {name!r}")
            return
        elif buffers is not None and name in buffers:
            if value is None or isinstance(value, Tensor):
                buffers[name] = value
            else:
                raise TypeError(
                    f"cannot assign {type(value)} to buffer {name!r}")
            return
        else:
            object.__setattr__(self, name, value)
            return
        object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name):
        _strip(self, name)
        if name in self.__dict__:
            object.__delattr__(self, name)

    def add_sublayer(self, name, sublayer):
        if not isinstance(sublayer, Layer) and sublayer is not None:
            raise TypeError("sublayer must be a Layer")
        self._sub_layers[str(name)] = sublayer
        if sublayer is not None:
            sublayer._named(str(name))
        return sublayer

    def _named(self, name):
        """This layer was registered under ``name`` in a parent: what
        ``__call__`` names its operations by in a traced program. A layer
        shared by two parents keeps the name given last."""
        object.__setattr__(self, "_name_scope", name)

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("parameter must be a Parameter")
        self._parameters[str(name)] = parameter
        return parameter

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            raise TypeError("buffer must be a Tensor")
        self._buffers[str(name)] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(str(name))
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None,
                         is_bias=False, default_initializer=None):
        """Create+register-later helper (reference layers.py
        create_parameter); caller assigns the result to an attribute."""
        from .. import initializer as init
        from ..initializer.attr import ParamAttr

        dtype = dtype_mod.convert_dtype(dtype) if dtype else \
            (self._dtype or dtype_mod.get_default_dtype())
        attr = ParamAttr._to_attr(attr)
        # Precedence per reference layer_helper_base.py:375-383: explicit
        # ParamAttr.initializer wins; otherwise set_global_initializer
        # overrides even the layer's default_initializer.
        g = init._get_global_initializer()
        if g is not None:
            g = g[1] if is_bias else g[0]
        if attr is not None and attr.initializer is not None:
            initializer = attr.initializer
        elif g is not None:
            initializer = g
        elif default_initializer is not None:
            initializer = default_initializer
        elif is_bias:
            initializer = init.Constant(0.0)
        else:
            initializer = init.XavierUniform()
        data = initializer(tuple(shape), dtype)
        p = Parameter(data, dtype=dtype,
                      name=attr.name if attr is not None else None)
        if attr is not None:
            p.need_clip = attr.need_clip
            if not attr.trainable:
                p.trainable = False
            p.optimize_attr["learning_rate"] = attr.learning_rate
        return p

    # -- traversal ---------------------------------------------------------
    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (f"{prefix}.{name}" if prefix else name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for n, p in layer.named_parameters(sub_prefix):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def parameters(self, include_sublayers=True):
        return [p for _, p in
                self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(sub_prefix)

    def buffers(self, include_sublayers=True):
        return [b for _, b in
                self.named_buffers(include_sublayers=include_sublayers)]

    def named_children(self) -> Iterator:
        for name, layer in self._sub_layers.items():
            if layer is not None:
                yield name, layer

    def children(self):
        for _, layer in self.named_children():
            yield layer

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, layer in self.named_children():
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from layer.named_sublayers(sub_prefix, include_self=True)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def apply(self, fn: Callable):
        for layer in self.children():
            layer.apply(fn)
        fn(self)
        return self

    # -- modes -------------------------------------------------------------
    def train(self):
        self.training = True
        for layer in self.children():
            layer.train()
        return self

    def eval(self):
        self.training = False
        for layer in self.children():
            layer.eval()
        return self

    # -- hooks -------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- execution ---------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        # a traced program's operations carry the layer's name in their
        # path (``.../self_attn/o_proj/dot_general``); an eager call has
        # no path to carry, and does not pay for the scope
        if self._name_scope is None or _trace_state_clean():
            return self._run(inputs, kwargs)
        with jax.named_scope(self._name_scope):
            return self._run(inputs, kwargs)

    def _run(self, inputs, kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    # -- state -------------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else \
            collections.OrderedDict()
        for name, p in self.named_parameters(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            dest[name] = p
        for name, b in self.named_buffers(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            short = name.rsplit(".", 1)[-1]
            owner = self
            if "." in name:
                for part in name.split(".")[:-1]:
                    owner = owner._sub_layers[part]
            if short not in owner._non_persistable_buffer_names:
                dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        current = self.state_dict()
        missing, unexpected = [], []
        for name, value in state_dict.items():
            if name not in current:
                unexpected.append(name)
                continue
            tgt = current[name]
            arr = value.numpy() if isinstance(value, Tensor) else \
                np.asarray(value)
            tgt.set_value(arr)
        for name in current:
            if name not in state_dict:
                missing.append(name)
        return missing, unexpected

    load_dict = set_state_dict

    # -- conversion --------------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        def convert(t):
            if t is None:
                return
            if dtype is not None and dtype_mod.is_floating_point(t.dtype):
                t._rebind(t._data.astype(dtype_mod.convert_dtype(dtype)))
            if device is not None:
                import jax

                from ...core.place import Place
                t._rebind(jax.device_put(t._data,
                                         Place.parse(device).jax_device()))
        for _, p in self.named_parameters():
            convert(p)
        for _, b in self.named_buffers():
            convert(b)
        if dtype is not None:
            for layer in self.sublayers(include_self=True):
                layer._dtype = dtype_mod.convert_dtype(dtype)
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def half(self):
        return self.to(dtype="float16")

    def clear_gradients(self, set_to_zero=False):
        for p in self.parameters():
            p.clear_gradient(set_to_zero)

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self.named_children():
            mod_str = repr(layer)
            mod_str = _addindent(mod_str, 2)
            lines.append(f"({name}): {mod_str}")
        main = type(self).__name__ + "(" + extra
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"


def _strip(layer, name):
    layer._parameters.pop(name, None)
    layer._sub_layers.pop(name, None)
    layer._buffers.pop(name, None)


def _addindent(s, n):
    lines = s.split("\n")
    if len(lines) == 1:
        return s
    return lines[0] + "\n" + "\n".join(" " * n + l for l in lines[1:])
