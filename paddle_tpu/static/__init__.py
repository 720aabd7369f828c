"""`paddle.static` compatibility surface.

The reference's legacy static-graph mode (python/paddle/static/: Program /
Executor / feed-fetch) has no TPU-native analogue — the compiled path is
`paddle_tpu.jit` (trace once, XLA executes). This module keeps the most-
used static entry points working by mapping them onto that path:
`InputSpec`/`data` declare signatures, `save/load_inference_model` persist
a network + params for the inference Predictor, and Executor/Program
raise with precise migration guidance instead of silently diverging.
"""

from __future__ import annotations

import numpy as np

from ..core import dtype as dtype_mod

__all__ = ["InputSpec", "data", "save_inference_model", "accuracy",
           "auc", "cpu_places", "cuda_places", "create_parameter",
           "create_global_var", "device_guard", "global_scope", "Print",
           "Variable", "WeightNormParamAttr", "ExponentialMovingAverage",
           "BuildStrategy", "CompiledProgram", "IpuStrategy",
           "IpuCompiledProgram", "append_backward", "serialize_program",
           "deserialize_program", "serialize_persistables",
           "deserialize_persistables", "ctr_metric_bundle", "save", "load",
           "save_to_file", "load_from_file", "load_program_state",
           "set_program_state", "normalize_program", "scope_guard",
           "py_func", "xpu_places", "ipu_shard_guard", "set_ipu_shard",
           "load_inference_model", "Program", "Executor",
           "default_main_program", "default_startup_program",
           "program_guard", "name_scope", "gradients"]


class InputSpec:
    """reference paddle.static.InputSpec (python/paddle/static/
    input.py)."""

    def __init__(self, shape, dtype="float32", name=None,
                 stop_gradient=True):
        self.shape = list(shape)
        self.dtype = dtype_mod.convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    @classmethod
    def from_tensor(cls, tensor, name=None):
        return cls(tensor.shape, tensor.dtype, name or tensor.name)

    @classmethod
    def from_numpy(cls, ndarray, name=None):
        return cls(list(ndarray.shape), str(ndarray.dtype), name)

    def batch(self, batch_size):
        return InputSpec([batch_size] + self.shape, self.dtype, self.name)

    def unbatch(self):
        return InputSpec(self.shape[1:], self.dtype, self.name)

    def __repr__(self):
        return (f"InputSpec(shape={self.shape}, dtype={self.dtype}, "
                f"name={self.name})")


def data(name, shape, dtype="float32", lod_level=0):
    return InputSpec(shape, dtype, name)


def save_inference_model(path_prefix, feed_vars, fetch_vars, executor=None,
                         program=None, **kwargs):
    """Persist params of the layer owning ``fetch_vars`` for the
    Predictor. In the eager front end the common call form is
    save_inference_model(prefix, layer_or_specs, layer, ...)."""
    from ..framework.io import save
    layer = None
    for cand in (fetch_vars, executor, program):
        if hasattr(cand, "state_dict"):
            layer = cand
            break
    if layer is None:
        raise ValueError(
            "save_inference_model: pass the Layer as fetch_vars "
            "(TPU-native deployment serializes params + a network factory; "
            "see paddle_tpu.inference.Config)")
    save(layer.state_dict(), path_prefix + ".pdiparams")


def load_inference_model(path_prefix, executor=None, **kwargs):
    from ..framework.io import load
    return load(path_prefix + ".pdiparams")


_MIGRATION = (
    "the legacy static-graph Program/Executor does not exist in "
    "paddle_tpu: decorate your model/step with paddle_tpu.jit.to_static "
    "or use paddle_tpu.jit.TrainStep — the traced function IS the "
    "program, compiled and scheduled by XLA")


class Program:
    def __init__(self):
        raise NotImplementedError(_MIGRATION)


class Executor:
    def __init__(self, place=None):
        raise NotImplementedError(_MIGRATION)


def default_main_program():
    raise NotImplementedError(_MIGRATION)


def default_startup_program():
    raise NotImplementedError(_MIGRATION)


class program_guard:
    def __init__(self, *a, **k):
        raise NotImplementedError(_MIGRATION)


class name_scope:
    """``with paddle.static.name_scope("block1"):`` names the operations
    traced inside the block: the segment shows in their path in a
    compiled program (``jit(f)/block1/dot_general``) and so in a
    profiler trace, beside the names ``Layer.__call__`` and
    ``profiler.tracing.scope`` give. Metadata only: an eager operation
    has no path and the computation is the same either way."""

    def __init__(self, name=""):
        self.name = name
        self._scope = None

    def __enter__(self):
        if self.name:
            import jax
            self._scope = jax.named_scope(self.name)
            self._scope.__enter__()
        return self

    def __exit__(self, *exc):
        scope, self._scope = self._scope, None
        if scope is not None:
            scope.__exit__(*exc)
        return False


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    from ..core.autograd import grad
    return grad(targets, inputs, grad_outputs=target_gradients,
                retain_graph=True, allow_unused=True)


# -- runnable pieces of the static surface ------------------------------

def accuracy(input, label, k=1, correct=None, total=None, name=None):
    from ..metric import accuracy as _acc
    return _acc(input, label, k=k)


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1,
        slide_steps=1, ins_tag_weight=None):
    from ..metric import Auc
    m = Auc(curve=curve, num_thresholds=num_thresholds)
    m.update(input, label)
    from ..core.tensor import Tensor
    import numpy as np
    return Tensor(np.float32(m.accumulate()))


def cpu_places(device_count=None):
    from ..core.place import Place
    n = device_count or 1
    return [Place("cpu", i) for i in range(n)]


def cuda_places(device_ids=None):
    """Accelerator places (the accelerator here is the TPU)."""
    import jax

    from ..core.place import Place
    ids = device_ids if device_ids is not None else \
        range(jax.device_count())
    return [Place("tpu", i) for i in ids]


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from ..compat_toplevel import create_parameter as _cp
    return _cp(shape, dtype, name=name, attr=attr, is_bias=is_bias,
               default_initializer=default_initializer)


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    import jax.numpy as jnp

    from ..core.dtype import convert_dtype
    from ..core.tensor import Tensor
    t = Tensor(jnp.full(shape, value, convert_dtype(dtype)))
    t.persistable = persistable
    if name:
        t.name = name
    return t


class device_guard:
    """Reference static.device_guard: context pinning ops to a device.
    XLA owns placement; accepted and recorded for compatibility."""

    def __init__(self, device=None):
        self.device = device

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def global_scope():
    class _Scope:
        def var(self, name):
            return None

        def find_var(self, name):
            return None
    return _Scope()


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_layout=True,
          print_tensor_lod=True, print_phase="both"):
    """Reference static.Print: identity that logs the value."""
    msg = message or "Print"
    print(f"{msg}: shape={list(input.shape)} dtype={input.dtype}")
    print(input.numpy() if hasattr(input, "numpy") else input)
    return input


from ..core.tensor import Tensor as Variable  # noqa: E402,F401


class WeightNormParamAttr:
    """Reference WeightNormParamAttr: weight-normalized parameter config
    (paddle_tpu applies weight norm through nn.utils-style reparam at
    layer level)."""

    def __init__(self, dim=None, name=None, initializer=None,
                 learning_rate=1.0, regularizer=None, trainable=True,
                 do_model_average=False, need_clip=True):
        self.dim = dim
        self.name = name
        self.initializer = initializer


class ExponentialMovingAverage:
    """Reference static.ExponentialMovingAverage, eager-native: tracks
    EMA shadows of every trainable parameter; apply()/restore() swap them
    in and out (the evaluation pattern)."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self.decay = decay
        self._shadow = {}
        self._backup = {}
        self._params = []
        self._step = 0

    def register(self, parameters):
        import jax.numpy as jnp
        self._params = [p for p in parameters if not p.stop_gradient]
        for p in self._params:
            self._shadow[id(p)] = p._data.astype(jnp.float32)

    def update(self, parameters=None):
        import jax.numpy as jnp
        if parameters is not None and not self._params:
            self.register(parameters)
        self._step += 1
        d = min(self.decay, (1 + self._step) / (10 + self._step))
        for p in self._params:
            old = self._shadow[id(p)]
            self._shadow[id(p)] = d * old + (1 - d) * p._data.astype(
                jnp.float32)

    def apply(self, executor=None, need_restore=True):
        ema = self

        class _Guard:
            def __enter__(self_g):
                for p in ema._params:
                    ema._backup[id(p)] = p._data
                    p._rebind(ema._shadow[id(p)].astype(p._data.dtype))
                return self_g

            def __exit__(self_g, *exc):
                if need_restore:
                    ema.restore()
                return False
        return _Guard()

    def restore(self, executor=None):
        for p in self._params:
            if id(p) in self._backup:
                p._rebind(self._backup.pop(id(p)))


class BuildStrategy:
    """Accepted-knob container (reference BuildStrategy; XLA owns
    scheduling/fusion)."""

    def __init__(self):
        self.enable_inplace = True
        self.fuse_elewise_add_act_ops = True
        self.memory_optimize = True


class CompiledProgram:
    def __init__(self, program, build_strategy=None):
        self.program = program
        self.build_strategy = build_strategy or BuildStrategy()


class IpuStrategy:
    def __init__(self):
        raise NotImplementedError("IPU is not a target of this build")


class IpuCompiledProgram:
    def __init__(self, *a, **k):
        raise NotImplementedError("IPU is not a target of this build")


def append_backward(loss, parameter_list=None, no_grad_set=None,
                    callbacks=None, checkpoints=None):
    raise NotImplementedError(
        "static program autodiff does not exist here; call "
        "loss.backward() (eager tape) or build a TrainStep (compiled)")


def serialize_program(feed_vars, fetch_vars, **kwargs):
    raise NotImplementedError(
        "ProgramDesc serialization n/a; use paddle_tpu.jit.save or "
        "onnx.export_stablehlo")


def deserialize_program(data):
    raise NotImplementedError(
        "ProgramDesc serialization n/a; use paddle_tpu.jit.load")


def serialize_persistables(feed_vars, fetch_vars, **kwargs):
    raise NotImplementedError("use paddle_tpu.save")


def deserialize_persistables(program, data, executor):
    raise NotImplementedError("use paddle_tpu.load")


def ctr_metric_bundle(input, label, ins_tag_weight=None):
    """CTR eval bundle (reference ctr_metric_bundle): returns sqrerr,
    abserr, prob, q, pos, total as tensors."""
    import jax.numpy as jnp

    from ..core.dispatch import unwrap
    from ..core.tensor import Tensor
    pred = unwrap(input).reshape(-1).astype(jnp.float32)
    lab = unwrap(label).reshape(-1).astype(jnp.float32)
    sqrerr = jnp.sum((pred - lab) ** 2)
    abserr = jnp.sum(jnp.abs(pred - lab))
    prob = jnp.sum(pred)
    q = jnp.sum(pred)
    pos = jnp.sum(lab)
    total = jnp.asarray(pred.shape[0], jnp.float32)
    return tuple(Tensor(v) for v in
                 (sqrerr, abserr, prob, q, pos, total))


def save(program, model_path, protocol=4, **configs):
    raise NotImplementedError("static programs n/a; use paddle_tpu.save")


def load(program, model_path, executor=None, var_list=None):
    raise NotImplementedError("static programs n/a; use paddle_tpu.load")


def save_to_file(path, content):
    with open(path, "wb") as f:
        f.write(content if isinstance(content, bytes)
                else bytes(content))


def load_from_file(path):
    with open(path, "rb") as f:
        return f.read()


def load_program_state(model_path, var_list=None):
    """Load a paddle_tpu.save checkpoint as a flat numpy state dict."""
    import numpy as np

    from ..framework.io import load as _load
    state = _load(model_path)

    def to_np(tree, prefix=""):
        out = {}
        for k, v in tree.items():
            key = f"{prefix}{k}"
            if isinstance(v, dict):
                out.update(to_np(v, key + "."))
            elif hasattr(v, "numpy"):
                out[key] = np.asarray(v.numpy())
            else:
                out[key] = v
        return out
    return to_np(state) if isinstance(state, dict) else state


def set_program_state(program, state):
    raise NotImplementedError(
        "static programs n/a; call layer.set_state_dict(state)")


def normalize_program(program, feed_vars, fetch_vars, **kwargs):
    raise NotImplementedError("static programs n/a")


class scope_guard:
    def __init__(self, scope):
        self.scope = scope

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Reference static.py_func: in eager-first design python functions
    run directly; apply func and return its output."""
    result = func(x)
    return result if result is not None else out


def xpu_places(device_ids=None):
    return cuda_places(device_ids)


class ipu_shard_guard:
    def __init__(self, index=-1, stage=-1):
        raise NotImplementedError("IPU is not a target of this build")


def set_ipu_shard(call_func, index=-1, stage=-1):
    raise NotImplementedError("IPU is not a target of this build")
