"""Names inside a compiled program (``profiler.tracing.scope``,
``Layer.__call__``, ``paddle.static.name_scope``): every matmul of every
serving program and of the train step says which part of the model it
is, and the computation is the same with or without the names.

Tiny engines of the four served models and a tiny GPT ``TrainStep`` are
compiled here with the CPU's compiler; the paths are read with the
benchmark's own parser (``benchmarks/scope_reduce.py``), so what is
checked is what the per-layer metrics read.
"""

import contextlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import paddle_tpu as paddle  # noqa: E402
from benchmarks import scope_reduce  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.models import Llama, LlamaConfig  # noqa: E402
from paddle_tpu.models.gpt import GPT, GPTConfig  # noqa: E402
from paddle_tpu.models.jamba import Jamba, JambaConfig  # noqa: E402
from paddle_tpu.models.llama import PagedServingModel  # noqa: E402
from paddle_tpu.models.sdar import SDAR, SDARConfig  # noqa: E402
from paddle_tpu.models.xing import Xing, XingConfig  # noqa: E402
from paddle_tpu.profiler import tracing  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402

_MODELS = {"llama": lambda: Llama(LlamaConfig.tiny()),
           "sdar": lambda: SDAR(SDARConfig.tiny()),
           "jamba": lambda: Jamba(JambaConfig.tiny()),
           "xing": lambda: Xing(XingConfig.tiny())}


def _abstract(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype
                                       if not hasattr(a, "dtype")
                                       else a.dtype), tree)


def served_programs(model, **engine):
    """{(job, shapes): optimized HLO text} of every serving program a
    tiny engine of ``model`` ran for two prompts that share a prefix:
    the prefill, the extend of the prefix hit and the decode (or block)
    step, compiled again from the shapes they were called with."""
    seen = {}
    plain = PagedServingModel.paged_call_args

    def recording(self, cache, job, head, tail=(), mode=None):
        program, args = plain(self, cache, job, head, tail, mode)
        key = (job, tuple(np.shape(a) for a in jax.tree.leaves((head,
                                                                tail))))
        seen.setdefault(key, (program, _abstract(args)))
        return program, args

    PagedServingModel.paged_call_args = recording
    try:
        eng = ServingEngine(model, **dict(dict(
            temperature=0.0, dtype=jnp.float32, max_batch=3, block_size=8,
            max_seq_len=64, bucket_cap=64, background=False,
            paged_kernel="pallas"), **engine))
        rng = np.random.default_rng(0)
        shared = rng.integers(3, 200, size=20)
        for n in (5, 12):
            eng.submit(np.concatenate([shared, rng.integers(3, 200, size=n)]),
                       max_new_tokens=4)
            eng.run_until_idle()
        if model.recurrent_state is not None:
            # state cannot be shared, so no prefix hit reaches the extend
            # program: continue a slot that was just allocated
            model.paged_prefill_extend(eng.cache, eng.cache.alloc_slot(6),
                                       shared[:6], 0, 0,
                                       kernel_mode="pallas")
        eng.close()
    finally:
        PagedServingModel.paged_call_args = plain
    held = model._param_arrays()
    texts = {}
    for key, (program, args) in seen.items():
        try:
            texts[key] = program._jitted.lower(*args).compile().as_text()
        finally:  # lowering left tracers in the parameters
            model._param_rebind()(held)
    return texts


def heavy_paths(text):
    """The ``op_name`` of every ``dot``, ``convolution`` and
    ``custom-call`` of a module's text ("" where it has none)."""
    return [named for body in scope_reduce._computations(text).values()
            for _n, opcode, named, _c, _o, _r in body
            if opcode in scope_reduce._HEAVY]


def _check_paths(paths, stack_segment):
    """Every path has a catalogued component and, where that is a
    sublayer of the stack, a ``<stack_segment>.<i>``. The CPU's compiler
    rewrites batched dots into ones it leaves without any path, not even
    the program's name: those are its own and say nothing (a matmul the
    program traced outside every scope has the path ``jit(<program>)/
    dot_general``, and fails here)."""
    named = [p for p in paths if p]
    assert len(named) >= 0.5 * len(paths) > 0, (len(named), len(paths))
    for path in named:
        component, _pass, layer, _sub, _op = scope_reduce.parse_op_name(path)
        assert component in tracing.SCOPE_NAMES, path
        if component in ("attn", "ffn", "mixer"):
            assert layer and re.fullmatch(stack_segment + r"\.\d+", layer), \
                path


@pytest.mark.parametrize("name", sorted(_MODELS))
def test_every_matmul_of_a_serving_program_says_its_component(name):
    model = _MODELS[name]()
    model.eval()
    texts = served_programs(model)
    jobs = {job for job, _shapes in texts}
    assert {"prefill", "extend"} <= jobs and jobs & {"decode", "block_step"}
    components = set()
    for text in texts.values():
        paths = heavy_paths(text)
        _check_paths(paths, "layers")
        components |= {scope_reduce.parse_op_name(p)[0] for p in paths if p}
    want = {"attn", "ffn", "head"} | ({"mixer"} if name == "jamba" else set())
    assert want <= components, components


@pytest.fixture(scope="module")
def train_text():
    model = GPT(GPTConfig.tiny())
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    step = paddle.jit.TrainStep(model, opt,
                                step_fn=lambda m, ids: m.loss(ids, ids))
    ids = paddle.to_tensor(jnp.ones((2, 16), jnp.int64))
    return step.lower(ids).compile().as_text()


def test_every_matmul_of_the_train_step_says_its_component(train_text):
    _check_paths(heavy_paths(train_text), "h")


def test_the_backward_carries_the_scopes_of_its_forward(train_text):
    """The tape re-enters the scopes a node was recorded under, so the
    pullback's matmuls are the component's and not nobody's."""
    parsed = [scope_reduce.parse_op_name(p)
              for p in heavy_paths(train_text) if p]
    backward = [(c, sub) for c, which, _l, sub, _op in parsed
                if which == "backward"]
    assert {"attn", "ffn"} <= {c for c, _sub in backward}
    assert any(sub.startswith("pt.attn/attn/qkv_proj") for _c, sub in backward)
    assert any(sub.startswith("pt.ffn/mlp/fc_out") for _c, sub in backward)


def test_the_update_is_the_optimizers(train_text):
    paths = re.findall(r'op_name="([^"]*)"', train_text)
    owners = {scope_reduce.parse_op_name(p)[0] for p in paths}
    assert "optimizer" in owners
    assert any("pt.optimizer" in p and p.endswith("sqrt") for p in paths)


def test_an_unknown_component_is_refused():
    with pytest.raises(KeyError):
        tracing.scope("attention")
    assert set(tracing.SCOPE_NAMES) == set(scope_reduce.COMPONENTS)
    for name in tracing.SCOPE_NAMES:
        with tracing.scope(name):
            pass


class _Named(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(4, 4)
        self.add_sublayer("extra", nn.Linear(4, 4))
        self.many = nn.LayerList([nn.Linear(4, 4)])
        self.many.append(nn.Linear(4, 4))
        self.seq = nn.Sequential(nn.Linear(4, 4), nn.Linear(4, 4))

    def forward(self, x):
        x = self.extra(self.fc(x))
        for layer in self.many:
            x = layer(x)
        with paddle.static.name_scope("block1"):
            x = x * 2.0
        return self.seq(x)


def _paths_of(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.fixture(scope="module")
def named_paths():
    model = _Named()

    def f(a):
        with paddle.no_grad():
            return model(paddle.to_tensor(a))._data
    return _paths_of(f, jnp.ones((2, 4)))


@pytest.mark.parametrize("segment", [
    "fc",            # __setattr__
    "extra",         # add_sublayer
    "many.0",        # a LayerList child: the list's name and its index
    "many.1",        # .. appended after the list was given its name
    "seq/0",         # a Sequential is called, so it is a segment itself
    "seq/1",
    "block1",        # paddle.static.name_scope
])
def test_a_layer_is_named_by_where_it_was_registered(named_paths, segment):
    assert any(re.search(rf"^jit\(f\)/{segment}/", p) for p in named_paths), \
        sorted(named_paths)


def test_a_list_renames_its_children_when_it_is_renamed_or_reordered():
    holder = nn.Layer()
    blocks = nn.LayerList([nn.Linear(2, 2), nn.Linear(2, 2)])
    assert [b._name_scope for b in blocks] == ["0", "1"]
    holder.layers = blocks
    assert [b._name_scope for b in blocks] == ["layers.0", "layers.1"]
    first = blocks[0]
    blocks.insert(0, nn.Linear(2, 2))
    assert first._name_scope == "layers.1"
    del blocks[0]
    assert first._name_scope == "layers.0"
    assert nn.Linear(2, 2)._name_scope is None  # a root has no name


def test_an_eager_call_enters_no_scope(monkeypatch):
    """Outside a trace there is no path to carry: the call does not pay
    for the scope."""
    from paddle_tpu.nn.layer import layers

    def refuse(name):
        raise AssertionError(f"eager call entered the scope {name!r}")

    model = _Named()
    monkeypatch.setattr(layers.jax, "named_scope", refuse)
    out = model.fc(paddle.to_tensor(np.ones((2, 4), np.float32)))
    assert out.shape == [2, 4]


def _stripped(text):
    """A module's text without what a name changes: the metadata of its
    instructions and the tables of files and stack frames."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    text = re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|StackFrames"
                  r"|\d+ [\"{].*)$", "", text)
    return re.sub(r"\n{2,}", "\n", text)


def test_the_scopes_change_nothing_but_the_names(monkeypatch):
    """With metadata stripped, the optimized decode program of a tiny
    engine equals what the same code gives with every scope patched to
    a no-op."""
    def decode_text():
        model = Llama(LlamaConfig.tiny())
        model.eval()
        texts = served_programs(model, paged_kernel=None)
        return next(t for (job, _s), t in texts.items() if job == "decode")

    paddle.seed(0)
    scoped = decode_text()
    assert "pt.attn" in scoped and "layers.1" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    monkeypatch.setattr(tracing, "_named_scope",
                        lambda name: contextlib.nullcontext())
    paddle.seed(0)
    bare = decode_text()
    assert "pt.attn" not in bare and "layers.1" not in bare
    assert _stripped(scoped) == _stripped(bare)
