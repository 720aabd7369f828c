"""Shared serving-test scaffolding for the decode speed tiers.

tools/spec_gate.py, tests/framework/test_spec_decode.py, and
tests/framework/test_quantization.py all pin their floors against the
SAME engine configuration — one tiny float32 Llama served with
max_batch=4, block_size=8, max_seq_len=64, bucket_cap=32, greedy. The
two test files take it from here so a config tweak cannot silently
make them measure different engines (the gate, a standalone tool,
keeps its own copy of the same literals and its docstring pins them
to this file).
"""

import pytest

import paddle_tpu as paddle


@pytest.fixture(scope="session")
def tiny_llama():
    from paddle_tpu.models import Llama, LlamaConfig

    paddle.seed(0)
    m = Llama(LlamaConfig.tiny())
    m.eval()
    return m


def tiny_engine(model, **kw):
    """The pinned serving-test engine (greedy, float32, synchronous)."""
    import jax.numpy as jnp

    from paddle_tpu.serving import ServingEngine

    kw.setdefault("max_batch", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("bucket_cap", 32)
    return ServingEngine(model, temperature=0.0, background=False,
                         dtype=jnp.float32, **kw)


# -- the KV pools are buffers, written in place (ISSUE 27) -----------------
# shared by test_paged_decode.py, test_prefix_cache.py, test_spec_decode.py
# and test_mesh_serving.py: each holds its own programs to the same rules

def pools_numpy(cache):
    """Copies: on the CPU ``np.asarray`` is a view that keeps the buffer
    alive, and a buffer somebody else holds is not donated."""
    import numpy as np

    return [np.array(a, copy=True) for a in cache.pool_arrays()]


def assert_pools_equal(got, want, scale_ulp=0):
    """Bitwise, pool by pool (both from :func:`pools_numpy`).
    ``scale_ulp`` lets an int8 cache's scale arrays ([blocks, block,
    heads] float32) differ by that many units in the last place: a
    program divides the absmax by 127 as XLA fuses it, the eager op as
    it stands alone, and the int8 rows still agree bit for bit."""
    import numpy as np

    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype and g.shape == w.shape, f"pool {i}"
        if scale_ulp and g.ndim == 3:
            np.testing.assert_array_max_ulp(g, w, maxulp=scale_ulp)
        else:
            assert np.array_equal(g, w), f"pool {i}"


def assert_lowered_donates(lowered, donated_args):
    """``lowered`` (``jitted.lower(...)``) marks exactly the leaves of
    the positional arguments ``donated_args`` as donated, in its
    argument table and in its text (``tf.aliasing_output`` where jax
    could pair the input with an output, ``jax.buffer_donor`` where it
    leaves the pairing to XLA)."""
    import re

    import jax

    args, _kwargs = lowered.args_info
    want = sum(len(jax.tree_util.tree_leaves(args[i]))
               for i in donated_args)
    assert want > 0
    for i, arg in enumerate(args):
        flags = [leaf.donated for leaf in jax.tree_util.tree_leaves(arg)]
        assert all(flags) if i in donated_args else not any(flags), i
    text = lowered.as_text()
    sig = text[text.index("func.func public @main("):]
    sig = sig[:sig.index(") -> ")]
    marked = re.findall(r"tf\.aliasing_output|jax\.buffer_donor", sig)
    assert len(marked) == want, (len(marked), want)


def undonated_twin(program):
    """The same traced function as a serving program (an
    ``aot_cache.AOTFunction``) under a plain ``jax.jit``: the same
    scatters on the same values into fresh buffers, its inputs left
    alive — what the donated program is compared with bitwise."""
    import jax

    return jax.jit(program._jitted.__wrapped__)


def dispatches(fn):
    """Run ``fn()`` under a profiler session; return (its result, the
    names of the programs the host dispatched meanwhile: one entry a
    ``PjitFunction(<name>)`` event, less those nested in another of the
    same name, which the runtime writes for the same call)."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            out = fn()
        path, = glob.glob(d + "/plugins/profile/*/*.xplane.pb")
        names = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                end, last = -1, None
                for ev in sorted(line.events, key=lambda e: e.start_ns):
                    if not ev.name.startswith("PjitFunction("):
                        continue
                    if ev.name == last and ev.start_ns <= end:
                        continue
                    last, end = ev.name, ev.start_ns + ev.duration_ns
                    names.append(ev.name[len("PjitFunction("):-1])
    return out, names
