"""The scope reduction (``benchmarks/scope_reduce.py``) and the seven
``busy_in_*_share`` / ``busy_unscoped_share`` readers, on a synthetic
trace reckoned by hand (``fixtures/scopes.xplane.txt`` with the module
``fixtures/scopes.hlo.txt``) and on a tiny engine's real compiled decode
program. All on the CPU, in this process.

The synthetic slice holds two programs on chip 0. ``jit_llama_paged_
decode`` (its module is the HLO fixture): ``fusion.3`` 0-4 us, the
``o_proj`` dot fused with the next norm's sum of squares, on which the
compiler left the norm's name; ``copy-done.2`` 4-5, the prefetch of
``o_proj``'s weight, with no name; ``while.5`` 6-12 around ``fusion.7``
(``up_proj``) 7-9 and 9.5-11.5; ``copy.9`` 12-13, which only the
result consumes; the paged kernel 13-15, which the module does not hold
and the event's own ``tf_op`` names. ``jit_train_step`` has no module:
``fusion.1`` 16-19 (a backward matmul of ``fc_out``) and ``fusion.2``
19-20 (the optimizer) by their ``tf_op``. Busy 18 us: attn 4 + 1 + 2,
ffn 4 + 2 (the loop's own time) + 3, optimizer 1, unscoped 1.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, scope_reduce, trace_reduce  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
US = 1e-6
DECODE = "jit_llama_paged_decode"
SERVE_CELLS = ["mistral7b-batch-saturated", "sdar30b-blockdiff-saturated",
               "jamba3b-reasoning-saturated", "xing29b-reasoning-saturated"]


def _read_fixture(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return f.read()


def _space(text):
    from google.protobuf import text_format

    return text_format.Parse(text, scope_reduce._xplane_pb2().XSpace())


def _with_module(space, program_id, name, hlo_text):
    """``space`` with the plane a chip's trace keeps its programs in:
    an event metadata a program whose ``Hlo Proto`` stat is the
    serialized ``xla.HloProto`` (its field 1 the module)."""
    from jax._src.lib import xla_client

    module = xla_client._xla.hlo_module_from_text(
        hlo_text).as_serialized_hlo_module_proto()
    size, varint = len(module), b""
    while True:
        varint += bytes([(size & 0x7F) | (0x80 if size > 0x7F else 0)])
        size >>= 7
        if not size:
            break
    plane = space.planes.add(name="/host:metadata")
    plane.stat_metadata[1].id = 1
    plane.stat_metadata[1].name = "Hlo Proto"
    em = plane.event_metadata[program_id]
    em.id = program_id
    em.name = f"{name}({program_id})"
    em.stats.add(metadata_id=1, bytes_value=b"\x0a" + varint + module)
    return space


@pytest.fixture(scope="module")
def hlo_text():
    return _read_fixture("scopes.hlo.txt")


@pytest.fixture(scope="module")
def reduced(hlo_text):
    """The fixture reduced with its module in the file (source (b))."""
    return scope_reduce.reduce_space(_with_module(
        _space(_read_fixture("scopes.xplane.txt")), 111, DECODE, hlo_text))


# -- the path of an operation -------------------------------------------------

@pytest.mark.parametrize("op_name, want", [
    ("jit(llama_paged_decode)/layers.3/pt.attn/self_attn/o_proj/dot_general",
     ("attn", "forward", "layers.3", "pt.attn/self_attn/o_proj",
      "dot_general")),
    # the colon XProf's ``tf_op`` ends in
    ("jit(xing_paged_decode)/layers.1/pt.ffn/mlp/dispatch/sort:",
     ("ffn", "forward", "layers.1", "pt.ffn/mlp/dispatch", "sort")),
    # the tape's pullback, re-entered under the forward's scopes
    ("jit(train_step)/h.0/pt.attn/attn/qkv_proj/jit(bwd)/transpose(jvp())"
     "/dot_general",
     ("attn", "backward", "h.0", "pt.attn/attn/qkv_proj", "dot_general")),
    # .. and the forward it runs again in front of the transpose
    ("jit(train_step)/h.1/pt.residual/ln_1/jit(bwd)/jvp()/rsqrt",
     ("residual", "recompute", "h.1", "pt.residual/ln_1", "rsqrt")),
    # jax's own transformations wrap segments, not only follow them
    ("jit(step)/transpose(jvp(layers.2/pt.ffn/mlp/down_proj))/dot_general",
     ("ffn", "backward", "layers.2", "pt.ffn/mlp/down_proj", "dot_general")),
    ("jit(step)/jvp(layers.2/pt.ffn/mlp)/checkpoint/rematted_computation"
     "/tanh",
     ("ffn", "recompute", "layers.2", "pt.ffn/mlp", "tanh")),
    ("jit(sdar_block_step)/layers.0/pt.attn/self_attn/while/body/"
     "closed_call/cond/branch_1_fun/dot_general",
     ("attn", "forward", "layers.0", "pt.attn/self_attn", "dot_general")),
    # a loop itself: the operation is the loop
    ("jit(f)/layers.0/pt.ffn/mlp/while",
     ("ffn", "forward", "layers.0", "pt.ffn/mlp", "while")),
    # the innermost component owns the operation
    ("jit(f)/layers.0/pt.attn/self_attn/pt.residual/add",
     ("residual", "forward", "layers.0", "pt.attn/self_attn/pt.residual",
      "add")),
    # one inner function inlined at every layer's call site: the paths
    # come strung together, and the last is whole
    ("jit(sdar_block_step)/layers.5/pt.ffn/mlp/dispatch/jit(searchsorted)/"
     "jit(sdar_block_step)/layers.4/pt.ffn/mlp/dispatch/jit(searchsorted)/"
     "vmap()/while/body/closed_call/convert_element_type",
     ("ffn", "forward", "layers.4", "pt.ffn/mlp/dispatch",
      "convert_element_type")),
    # outside the stack: no layer
    ("jit(llama_paged_decode)/pt.head/lm_head/dot_general",
     ("head", "forward", None, "pt.head/lm_head", "dot_general")),
    ("jit(train_step)/pt.optimizer/sqrt",
     ("optimizer", "forward", None, "pt.optimizer", "sqrt")),
    # nothing named: a program from before the scopes, a stray operation
    ("jit(llama_paged_decode)/jit(fwd)/dot_general",
     (None, "forward", None, "", "dot_general")),
    # a layer's attribute is no component, whatever it is called
    ("jit(f)/pt.speed/mul", (None, "forward", None, "pt.speed", "mul")),
    ("", (None, "forward", None, "", "")),
])
def test_a_path_gives_component_pass_layer_and_sublayer(op_name, want):
    assert scope_reduce.parse_op_name(op_name) == want


# -- the map of one program ---------------------------------------------------

def test_a_fusion_goes_to_its_heaviest_instruction(hlo_text):
    scopes, inherited = scope_reduce.program_scopes(hlo_text)
    # the compiler left the norm's name on the fusion; its dot is o_proj's
    assert scope_reduce.parse_op_name(scopes["fusion.3"])[:4] == (
        "attn", "forward", "layers.0", "pt.attn/self_attn/o_proj")
    # no name on the fusion at all: its root's
    assert scope_reduce.parse_op_name(scopes["fusion.7"])[3] == \
        "pt.ffn/mlp/up_proj"
    assert "fusion.3" not in inherited and "fusion.7" not in inherited


def test_a_nameless_copy_is_named_by_what_consumes_it(hlo_text):
    scopes, inherited = scope_reduce.program_scopes(hlo_text)
    assert scopes["copy-done.2"] == scopes["copy-start.2"] == \
        scopes["fusion.3"]
    assert {"copy-done.2", "copy-start.2"} <= inherited
    # only the program's result consumes it: nobody's
    assert scopes["copy.9"] == "" and "copy.9" not in inherited
    # a loop keeps the name the compiler left on it
    assert scopes["while.5"].endswith("pt.ffn/mlp/while")


# -- the reduction ------------------------------------------------------------

def test_busy_time_splits_by_component_and_adds_up(reduced):
    assert reduced["marked"] and reduced["programs"] == [1, 1]
    assert reduced["busy_s"] == pytest.approx(18 * US)
    got = {k: v / US for k, v in reduced["by_component"].items()}
    assert got == pytest.approx({"attn": 7, "ffn": 9, "mixer": 0,
                                 "residual": 0, "head": 0, "optimizer": 1,
                                 "unscoped": 1})
    assert sum(reduced["by_component"].values()) == \
        pytest.approx(reduced["busy_s"])
    assert reduced["inherited_s"] == pytest.approx(1 * US)


def test_self_time_under_a_loop_is_not_counted_twice(reduced):
    rows = {k: v / US for k, v in reduced["rows"].items()}
    assert rows[("ffn", "forward", DECODE, "pt.ffn/mlp",
                 "while s32[]")] == pytest.approx(2)
    assert rows[("ffn", "forward", DECODE, "pt.ffn/mlp/up_proj",
                 "fusion bf16[8,256]")] == pytest.approx(4)


def test_the_rows_name_pass_program_sublayer_and_operation(reduced):
    rows = {k: v / US for k, v in reduced["rows"].items()}
    assert rows[("attn", "forward", DECODE, "pt.attn/self_attn/o_proj",
                 "fusion f32[8]")] == pytest.approx(4)
    # the copy's row says whose weight it fetched
    assert rows[("attn", "forward", DECODE, "pt.attn/self_attn/o_proj",
                 "copy-done bf16[64,64]")] == pytest.approx(1)
    # not in the module: the event's own ``tf_op``
    assert rows[("attn", "forward", DECODE, "pt.attn/self_attn",
                 "paged_decode_chunked bf16[8,4,16]")] == pytest.approx(2)
    # a program whose module the file lacks: ``tf_op``, the name from
    # its ``XLA Modules`` events
    assert rows[("ffn", "backward", "jit_train_step", "pt.ffn/mlp/fc_out",
                 "fusion bf16[64,256]")] == pytest.approx(3)
    assert rows[("unscoped", "forward", DECODE, "",
                 "copy bf16[8,64]")] == pytest.approx(1)
    assert sum(rows.values()) == pytest.approx(18)


def test_a_module_handed_over_beside_the_file_gives_the_same(hlo_text,
                                                              reduced):
    """Source (c): the program's own optimized text."""
    bare = _space(_read_fixture("scopes.xplane.txt"))
    handed = scope_reduce.reduce_space(bare, {DECODE: hlo_text})
    assert handed["programs"] == [1, 1]
    assert handed["rows"] == pytest.approx(reduced["rows"])


def test_without_any_module_the_events_own_names_are_used():
    """Source (a) alone: the fusion is what the compiler left on it, and
    the nameless copies and the loop's body are nobody's."""
    alone = scope_reduce.reduce_space(
        _space(_read_fixture("scopes.xplane.txt")))
    got = {k: v / US for k, v in alone["by_component"].items()}
    assert got == pytest.approx({"attn": 2, "ffn": 5, "mixer": 0,
                                 "residual": 4, "head": 0, "optimizer": 1,
                                 "unscoped": 6})
    assert alone["programs"] == [0, 2]


def test_a_trace_without_device_operations_reduces_to_none():
    assert scope_reduce.reduce_space(_space(
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" '
        'events { metadata_id: 1 offset_ps: 0 duration_ps: 5 } } '
        'event_metadata { key: 1 value { id: 1 name: "serving.step" } } }'
    )) is None


# -- the readers --------------------------------------------------------------

class _Cell:
    name = "synthetic"


def _ctx_on(tmp_path, monkeypatch, space):
    """A readers' ctx whose cell's trace is ``space``, written where the
    harness writes a cell's trace, under a TRACE_DIR of the test's own."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    where = tmp_path / _Cell.name / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(space.SerializeToString())
    return {"cell": _Cell(), "counters": {}, "trace": {"busy_s": 1.0}}


def _read(family, ctx):
    reader = harness.load_module(harness.reader_path(family))
    return reader.read(dict(ctx, metric=family + ".serve"))


FAMILIES = {"busy_in_attn_share": 100 * 7 / 18,
            "busy_in_ffn_share": 100 * 9 / 18,
            "busy_in_mixer_share": 0.0,
            "busy_in_residual_share": 0.0,
            "busy_in_head_share": 0.0,
            "busy_in_optimizer_share": 100 * 1 / 18,
            "busy_unscoped_share": 100 * 1 / 18}


@pytest.fixture()
def scoped_ctx(tmp_path, monkeypatch, hlo_text):
    return _ctx_on(tmp_path, monkeypatch, _with_module(
        _space(_read_fixture("scopes.xplane.txt")), 111, DECODE, hlo_text))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_reader_gives_its_components_share_of_busy(scoped_ctx, family):
    got = _read(family, scoped_ctx)
    assert got == pytest.approx(FAMILIES[family])
    if FAMILIES[family] == 0.0:
        # a model without a mixer: the trace has components, this one
        # has no operation
        assert got == 0.0 and got is not None


def test_the_seven_shares_add_up_to_100(scoped_ctx):
    assert sum(_read(f, scoped_ctx) for f in FAMILIES) == pytest.approx(100)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_reader_says_nothing_of_a_program_from_before_the_scopes(
        tmp_path, monkeypatch, family):
    """The parent commit's trace: operations, and no component in any
    path. Nor with no trace at all."""
    bare = _read_fixture("scopes.xplane.txt").replace("pt.", "")
    ctx = _ctx_on(tmp_path, monkeypatch, _space(bare))
    assert scope_reduce.of_cell(ctx)["marked"] is False
    assert _read(family, ctx) is None
    assert _read(family, dict(ctx, trace=None)) is None


def test_the_file_is_reduced_once_for_all_readers(scoped_ctx, monkeypatch):
    calls = []
    plain = scope_reduce.reduce_space
    monkeypatch.setattr(scope_reduce, "reduce_space",
                        lambda *a, **k: calls.append(1) or plain(*a, **k))
    for family in FAMILIES:
        _read(family, scoped_ctx)
    assert len(calls) == 1


def test_the_cli_prints_the_tables(scoped_ctx, capsys):
    path = trace_reduce.find_xplane(
        os.path.join(harness.TRACE_DIR, _Cell.name))
    assert scope_reduce.main([path, "3"]) == 0
    out = capsys.readouterr().out
    assert "busy by component" in out and "(5 more rows)" in out
    assert "pt.attn/self_attn/o_proj  [fusion f32[8]]" in out


# -- source (c) on a real program ---------------------------------------------

def test_a_real_decode_programs_fusions_land_in_their_components():
    """The map of a tiny engine's compiled decode program: the
    instruction that holds ``o_proj``'s dot is ``attn``, the one that
    holds ``down_proj``'s is ``ffn`` (a fusion where the compiler made
    one, as the chip's does; the dot itself where it did not), whatever
    else was fused in."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.inference.paged import PagedKVCache
    from paddle_tpu.models import Llama, LlamaConfig

    model = Llama(LlamaConfig.tiny())
    model.eval()
    cfg = model.config
    cache = PagedKVCache(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
                         num_blocks=9, block_size=8, max_batch=2,
                         max_blocks_per_seq=4, dtype=jnp.float32)
    held = model._param_arrays()
    program, args = model.paged_call_args(
        cache, "decode", (jnp.zeros((2,), jnp.int32),),
        (cache.block_tables, jnp.asarray(cache.seq_lens),
         jnp.asarray(np.ones((2,), bool)), jax.random.PRNGKey(0),
         jnp.float32(0.0)), mode="dense")
    try:
        text = program._jitted.lower(*args).compile().as_text()
    finally:
        model._param_rebind()(held)
    scopes, _inherited = scope_reduce.program_scopes(text)
    bodies = scope_reduce._computations(text)

    def dots_of(opcode, named, called, depth=0):
        """The paths of the dots an instruction is or holds."""
        if opcode == "dot":
            return [named]
        if opcode != "fusion" or depth > 4:
            return []
        return [p for _n, op, nm, cl, _o, _r in bodies.get(called, ())
                for p in dots_of(op, nm, cl, depth + 1)]

    found = {"o_proj/dot_general": "attn", "down_proj/dot_general": "ffn",
             "lm_head/dot_general": "head"}
    seen = set()
    entry = max(bodies.values(), key=len)
    for name, opcode, named, called, _o, _r in entry:
        for what, component in found.items():
            if any(p.endswith(what) for p in dots_of(opcode, named, called)):
                seen.add(what)
                assert scope_reduce.parse_op_name(scopes[name])[0] == \
                    component, (name, scopes[name])
    assert seen == set(found)


# -- the manifest -------------------------------------------------------------

def _new_entries():
    with open(harness.MANIFEST) as f:
        manifest = json.load(f)
    # a later cell may read these families under a tag of its own
    return manifest, [m for m in manifest["per_layer"]
                      if m["name"].split(".")[0] in FAMILIES
                      and m["name"].split(".")[1] in ("serve", "steady",
                                                      "train")]


def test_the_manifest_has_the_sixteen_entries():
    # the issue asked for seventeen; the manifest holds 128 per-layer metrics
    # at most and had 112, so the steady cell's residual share, which is 100
    # less its four others, is the one that is not listed
    manifest, mine = _new_entries()
    assert harness.manifest_problems(manifest) == []
    assert len(manifest["per_layer"]) <= 128
    assert len(mine) == 16
    first = manifest["per_layer"].index(mine[0])
    assert manifest["per_layer"][first:first + 16] == mine  # in one block
    by_tag = {}
    for m in mine:
        family, tag = m["name"].split(".")
        by_tag.setdefault(tag, []).append(family)
        assert m["unit"] == "%" and m["source"] == "device_trace"
        assert m["better"] == "lower"
        assert m["layer"] == ("train step" if tag == "train"
                              else "model step")
        assert (m["moves"], m["workloads"]) == {
            "serve": ("serve_tok_s", SERVE_CELLS),
            "steady": ("itl_p95_ms", ["mistral7b-chat-steady"]),
            "train": ("train_tok_s", ["gpt2m-train-seq1024"])}[tag]
    everywhere = {"busy_in_attn_share", "busy_in_ffn_share",
                  "busy_in_residual_share", "busy_in_head_share",
                  "busy_unscoped_share"}
    assert set(by_tag["serve"]) == everywhere | {"busy_in_mixer_share"}
    assert set(by_tag["steady"]) == everywhere - {"busy_in_residual_share"}
    assert set(by_tag["train"]) == everywhere | {"busy_in_optimizer_share"}


@pytest.mark.parametrize("cell, tag", [
    (c, "serve") for c in SERVE_CELLS] + [
    ("mistral7b-chat-steady", "steady"), ("gpt2m-train-seq1024", "train")])
def test_every_cell_reads_its_tags_shares_and_no_others(cell, tag):
    loaded = harness.load_cell(harness.MANIFEST, cell)
    mine = [m["name"] for m in loaded.per_layer
            if m["name"].split(".")[0] in FAMILIES]
    assert mine and all(n.endswith("." + tag) for n in mine)
    for name in mine:
        assert callable(harness.load_module(harness.reader_path(name)).read)
