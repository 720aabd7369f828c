"""From the same profiler trace (``*.xplane.pb``) as ``trace_reduce`` and
``span_reduce`` to what the program's *named scopes* say about the
device's busy time: which part of the model every operation is.

The program names regions of what it traces
(``paddle_tpu.profiler.tracing.scope``: ``pt.attn``, ``pt.ffn``,
``pt.mixer``, ``pt.residual``, ``pt.head``, ``pt.optimizer``; beneath a
component ``Layer.__call__`` adds the name a layer is registered under).
The path becomes the ``op_name`` of every HLO instruction traced inside:
``jit(llama_paged_decode)/layers.3/pt.attn/self_attn/o_proj/dot_general``.

What a v5e trace holds of it (looked at by hand with
``tensorflow.tsl.profiler.protobuf.xplane_pb2``, PR 36; jax's
``ProfileData`` shows an event's own stats only, ``device_offset_ps``,
``device_duration_ps`` and a time scale, and none of this):

(a) the *metadata* of an ``XLA Ops`` event (one an instruction of a
    program) has the stats ``program_id``, ``symbol_id``,
    ``hlo_category`` (``convolution fusion``, ``data formatting``,
    ``copy-start`` ..), ``flops``, ``bytes_accessed``, ``source`` and
    ``tf_op``, the instruction's own ``op_name`` with a colon behind it.
    A fusion has the one name the compiler left on it; a ``copy``, a
    ``copy-start`` / ``copy-done`` pair or a ``slice-done`` has none;
(b) the plane ``/host:metadata`` has one event metadata a program, its
    id the ``program_id`` and its name ``jit_<function>(<id>)``, whose
    stat ``Hlo Proto`` is the serialized ``xla.HloProto`` of the
    *optimized* module: every instruction with its ``op_name``, the
    fused computations among them. It is there for a program loaded from
    the compilation cache as for one compiled in the process;
(c) (not in a trace) the same text from the program itself:
    ``jitted.lower(..).compile().as_text()``, which is what the tests
    rehearse the map on with the CPU's compiler.

So the join is (program, instruction name) -> ``op_name``, from (b)
where the file has the program and from (a)'s ``tf_op`` where it has
not. ``program_scopes`` makes the map of one module's text:

- a fusion belongs to its heaviest instruction: a ``dot``,
  ``convolution`` or ``custom-call`` of its fused computation (or of a
  fusion nested in it) if there is one, else its root, else what the
  compiler left on the fusion. So ``o_proj`` fused with the next norm's
  sum of squares is ``attn``;
- an instruction without a name (the copies above) takes the name of
  what consumes it, through ``*-done`` and ``bitcast`` and the like, in
  its own computation: a weight-shaped ``copy-done`` in front of the
  ``down_proj`` fusion is the prefetch of that weight;
- everything else keeps its own.

``reduce_space`` returns, or None where no device operation was
recorded:

- ``busy_s``: chip 0's busy time as ``trace_reduce`` counts it, the sum
  of the operations' *self* time (an enclosing ``while`` less what runs
  inside it), so that the parts add up;
- ``marked``: whether any operation's path holds a component at all (a
  program from before the scopes has none, and the readers say None);
- ``by_component``: seconds by component, ``unscoped`` for the rest;
- ``rows``: seconds by (component, pass, program, sublayer path without
  the layer's index, operation with its result type). The pass is
  ``backward`` under a ``transpose(..)``, ``recompute`` under a
  ``checkpoint`` / ``remat`` or inside the tape's pullback ``jit(bwd)``
  outside its transpose (the forward it runs again), else ``forward``;
- ``inherited_s``: the part of ``busy_s`` named by a consumer;
- ``programs``: ``[with an HLO module in the file, without]``;
  ``reduce_s`` (``reduce_file``): what reading and reducing the file
  took.

``python -m benchmarks.scope_reduce <trace.xplane.pb> [rows]`` prints
the tables, the largest 40 rows of the second unless told otherwise.
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys
import time
import warnings

from benchmarks import trace_reduce
from benchmarks.trace_reduce import _DEVICE_PLANE, _OPS_LINE, _self_times

# the program's catalogue (``tracing.SCOPE_NAMES``), kept here as well:
# the yardstick reads a program from before the catalogue too
COMPONENTS = ("attn", "ffn", "mixer", "residual", "head", "optimizer")
UNSCOPED = "unscoped"
_MARK = "pt."
_METADATA_PLANE = "/host:metadata"
# a program's id is 64 bits that one place holds signed (a map's key, a
# stat) and another prints unsigned (a module event's name)
_U64 = (1 << 64) - 1
_HEAVY = ("dot", "convolution", "custom-call")
# instructions a value passes through unchanged on its way to the one
# that computes with it
_PASS_THROUGH = ("bitcast", "copy", "copy-start", "copy-done",
                 "slice-start", "slice-done", "get-tuple-element",
                 "dynamic-slice-start", "dynamic-slice-done",
                 "async-start", "async-done", "reshape", "transpose",
                 "slice", "dynamic-slice", "concatenate", "pad",
                 # the compiler's own, nameless: ``ConcatBitcast`` of a
                 # weight's prefetched slices
                 "custom-call")
_ROWS_SHOWN = 40


# -- the path of an operation --------------------------------------------------

_JIT = re.compile(r"jit\([^()/]*\)")
_WRAPPERS = frozenset((
    "jvp", "transpose", "vmap", "pmap", "checkpoint", "remat",
    "rematted_computation", "while", "body", "cond", "closed_call",
    "core_call", "custom_jvp_call", "custom_vjp_call", "pjit",
    "shard_map", "named", "xla_call"))
_BRANCH = re.compile(r"^branch_\d+_fun$")
_MODULE_EVENT = re.compile(r"^(jit_.*)\((\d+)\)$")
_LAYER = re.compile(r"^[A-Za-z_]\w*\.\d+$")


def parse_op_name(op_name):
    """``(component, pass, layer, sublayer, operation)`` of an
    instruction's ``op_name``: the innermost ``pt.<component>`` (None
    where the path has none), the pass, the layer's segment
    (``layers.3``, None outside the stack), the path from the component
    down to the operation without the transformations' wrappers, and the
    operation (the last segment)."""
    path = op_name.rstrip(":")
    program = path.split("/", 1)[0] + "/"
    if program.startswith("jit(") and path.count(program) > 1:
        # one inner function called from every layer and inlined: the
        # compiler strings the call sites' paths together; the last is
        # whole
        path = program + path.rsplit(program, 1)[1]
    if "transpose(" in path:
        which = "backward"
    elif "checkpoint" in path or "remat" in path or "jit(bwd)" in path:
        which = "recompute"
    else:
        which = "forward"
    raw = [t for t in re.split(r"[/()]+", _JIT.sub("", path)) if t]
    if not raw:
        return None, which, None, "", ""
    *raw, operation = raw
    tokens = [t for t in raw
              if t not in _WRAPPERS and not _BRANCH.match(t)]
    marks = [i for i, t in enumerate(tokens)
             if t.startswith(_MARK) and t[len(_MARK):] in COMPONENTS]
    component = tokens[marks[-1]][len(_MARK):] if marks else None
    first = marks[0] if marks else len(tokens)
    layer = next((t for t in tokens[:first] if _LAYER.match(t)), None)
    sublayer = "/".join(t for t in tokens if t != layer)
    return component, which, layer, sublayer, operation


# -- the map of one program -----------------------------------------------------

_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([^\s,)}]+)")
_REFERENCE = re.compile(r"%([^\s,(){}=]+)")


def _computations(hlo_text):
    """{computation: [(name, opcode, op_name, called computation,
    operands, is root)]} of a module's text."""
    out, current = {}, None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and current is not None:
            root, name, rest = m.groups()
            op = _OPCODE.search(rest)
            if op is None:
                continue
            named = _OP_NAME.search(rest)
            called = _CALLS.search(rest)
            current.append((
                name, op.group(1), named.group(1) if named else "",
                called.group(1) if called else None,
                _REFERENCE.findall(rest[op.end():].split(
                    ", metadata=")[0]), bool(root)))
        elif line.rstrip().endswith("{") and " = " not in line:
            head = line.strip().removeprefix("ENTRY").strip()
            name = re.split(r"[\s(]", head.lstrip("%"), maxsplit=1)[0]
            current = out.setdefault(name, [])
        elif line.strip() == "}":
            current = None
    return out


def program_scopes(hlo_text):
    """``({instruction: op_name}, instructions named by a consumer)`` of
    an optimized module's text (source (b) or (c) of the module's
    docstring), by the three rules there."""
    comps = _computations(hlo_text)

    def heavy_in(comp, depth=0):
        """The paths of the heavy instructions of ``comp`` and of the
        fusions nested in it, in order."""
        found = []
        for _n, opcode, named, called, _ops, _root in comps.get(comp, ()):
            if opcode in _HEAVY and named:
                found.append(named)
            elif opcode == "fusion" and called and depth < 4:
                found += heavy_in(called, depth + 1)
        return found

    def of_fusion(own, called):
        body = comps.get(called, ())
        heavy = heavy_in(called)
        if heavy:
            # two matmuls in one fusion (``up_proj`` and ``down_proj``
            # around the SwiGLU's product): the one the compiler named
            # the fusion by, else the first
            return own if own in heavy else heavy[0]
        by_name = {i[0]: i for i in body}
        root = next((i for i in body if i[5]), None)
        at_root = ""
        if root is not None:
            at_root = root[2] or next(
                (by_name[o][2] for o in root[4]
                 if o in by_name and by_name[o][2]), "")
        marked = next((i[2] for i in body if _MARK in i[2]), "")
        first = at_root or own
        # a root the compiler made up (a tuple, a bitcast) has a path
        # without a component: what was fused under one says more
        return first if _MARK in first or not marked else marked

    scopes, inherited = {}, set()
    for body in comps.values():
        for name, opcode, named, called, _ops, _root in body:
            scopes[name] = of_fusion(named, called) \
                if opcode == "fusion" and called else named
    for body in comps.values():
        users = {}
        for name, _opcode, _named, _called, operands, _root in body:
            for o in operands:
                users.setdefault(o, []).append(name)
        opcode_of = {i[0]: i[1] for i in body}
        for name, opcode, _named, _called, _ops, _root in body:
            if scopes[name] or opcode not in _PASS_THROUGH:
                continue
            seen, frontier = {name}, [name]
            for _hop in range(6):
                nxt = [u for f in frontier for u in users.get(f, ())
                       if u not in seen]
                found = next((scopes[u] for u in nxt if scopes.get(u)), "")
                if found:
                    scopes[name] = found
                    inherited.add(name)
                    break
                frontier = [u for u in nxt
                            if opcode_of.get(u) in _PASS_THROUGH]
                seen.update(nxt)
                if not frontier:
                    break
    return scopes, inherited


# -- reading the file whole -----------------------------------------------------

def _xplane_pb2():
    """``tensorflow.tsl.profiler.protobuf.xplane_pb2`` loaded by its
    file: the module is a serialized descriptor with no import but
    protobuf's own, and importing the package around it costs 15 s."""
    spec = importlib.util.find_spec("tensorflow")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    if "benchmarks._xplane_pb2" not in sys.modules:
        sub = importlib.util.spec_from_file_location(
            "benchmarks._xplane_pb2", path)
        mod = importlib.util.module_from_spec(sub)
        sub.loader.exec_module(mod)
        sys.modules["benchmarks._xplane_pb2"] = mod
    return sys.modules["benchmarks._xplane_pb2"]


def parse_space(data):
    space = _xplane_pb2().XSpace()
    space.ParseFromString(data)
    return space


def module_text(hlo_module_proto):
    """The text of a serialized ``xla.HloModuleProto``, metadata
    printed, through jaxlib's own printer."""
    from jax._src.lib import xla_client

    xla = xla_client._xla
    options = xla.HloPrintOptions()
    options.print_metadata = True
    options.print_backend_config = False
    options.print_large_constants = False
    return xla.HloModule.from_serialized_hlo_module_proto(
        hlo_module_proto).to_string(options)


def _hlo_module_of(hlo_proto):
    """Field 1 (``hlo_module``) of a serialized ``xla.HloProto``."""
    at, n = 0, len(hlo_proto)
    while at < n:
        key, shift = 0, 0
        while True:
            byte = hlo_proto[at]
            at += 1
            key |= (byte & 0x7F) << shift
            shift += 7
            if not byte & 0x80:
                break
        if key & 7 != 2:  # every field of HloProto is a message
            return None
        size, shift = 0, 0
        while True:
            byte = hlo_proto[at]
            at += 1
            size |= (byte & 0x7F) << shift
            shift += 7
            if not byte & 0x80:
                break
        if key >> 3 == 1:
            return hlo_proto[at:at + size]
        at += size
    return None


def _stats(plane, owner):
    """{stat name: value} of an event metadata's (or a plane's) stats."""
    out = {}
    for s in owner.stats:
        kind = s.WhichOneof("value")
        value = getattr(s, kind) if kind else None
        if kind == "ref_value":
            value = plane.stat_metadata[value].name
        out[plane.stat_metadata[s.metadata_id].name] = value
    return out


def programs_in(space):
    """{program id: (module name, {instruction: op_name}, inherited)} of
    the HLO modules the trace holds (source (b))."""
    out = {}
    for plane in space.planes:
        if plane.name != _METADATA_PLANE:
            continue
        for pid, em in plane.event_metadata.items():
            proto = _stats(plane, em).get("Hlo Proto")
            module = _hlo_module_of(proto) if proto else None
            if not module:
                continue
            try:
                text = module_text(module)
            except (RuntimeError, ValueError) as e:
                # jaxlib could not read the module back: the program's
                # events keep their own names (source (a))
                warnings.warn(f"scope_reduce: {em.name}: {e}")
                continue
            scopes, inherited = program_scopes(text)
            out[pid & _U64] = (_MODULE_EVENT.sub(r"\1", em.name), scopes,
                               inherited)
    return out


def reduce_space(space, texts=None):
    """The reduction of a parsed trace. ``texts`` is {program name:
    optimized HLO text} handed over beside the file (source (c)): used
    for the programs the file itself has no module of."""
    chips = []
    for plane in space.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == _OPS_LINE and len(line.events):
                chips.append((plane.name, plane, line))
    if not chips:
        return None
    _name, plane, line = min(chips, key=lambda c: c[0])
    base = line.timestamp_ns
    self_ns = _self_times(sorted(
        (base + e.offset_ps / 1e3, base + (e.offset_ps + e.duration_ps) / 1e3,
         e.metadata_id) for e in line.events))

    programs = programs_in(space)
    handed = {name: program_scopes(text)
              for name, text in (texts or {}).items()}
    # a program's name, where the file has no module of it: from its
    # events on the ``XLA Modules`` line, ``jit_<function>(<program id>)``
    named = {}
    for em in plane.event_metadata.values():
        m = _MODULE_EVENT.match(em.name)
        if m:
            named[int(m.group(2)) & _U64] = m.group(1)
    rows, by_component = {}, {c: 0.0 for c in COMPONENTS + (UNSCOPED,)}
    busy = inherited_s = 0.0
    marked = False
    with_module, without = set(), set()
    for mid, ns in self_ns.items():
        em = plane.event_metadata[mid]
        stats = _stats(plane, em)
        instruction = em.name.split(" = ", 1)[0].strip().lstrip("%")
        pid = int(stats.get("program_id") or 0) & _U64
        program, scopes, inherited = programs.get(
            pid, (named.get(pid, ""), None, ()))
        if scopes is None and program in handed:
            scopes, inherited = handed[program]
        (without if scopes is None else with_module).add(pid)
        op_name = (scopes or {}).get(instruction) or stats.get("tf_op") \
            or ""
        component, which, _layer, sublayer, _op = parse_op_name(op_name)
        marked = marked or component is not None
        sec = ns / 1e9
        busy += sec
        if instruction in inherited:
            inherited_s += sec
        by_component[component or UNSCOPED] += sec
        key = (component or UNSCOPED, which, program, sublayer,
               trace_reduce.op_key(em.name)[1])
        rows[key] = rows.get(key, 0.0) + sec
    return {"busy_s": busy, "marked": marked, "by_component": by_component,
            "rows": rows, "inherited_s": inherited_s,
            "programs": [len(with_module), len(without)]}


_BY_PATH = {}


def reduce_file(path):
    """``reduce_space`` of the trace at ``path``, made once a process
    and shared by the readers."""
    path = os.path.abspath(path)
    if path not in _BY_PATH:
        t0 = time.perf_counter()
        with open(path, "rb") as f:
            reduced = reduce_space(parse_space(f.read()))
        if reduced is not None:
            reduced["reduce_s"] = time.perf_counter() - t0
        _BY_PATH[path] = reduced
    return _BY_PATH[path]


def of_cell(ctx):
    """The reduction of the trace the harness wrote for the cell a
    reader's ``ctx`` belongs to; None where there is none."""
    from benchmarks import harness

    if ctx.get("trace") is None:
        return None
    path = trace_reduce.find_xplane(
        os.path.join(harness.TRACE_DIR, ctx["cell"].name))
    return reduce_file(path) if path else None


def share(ctx, component):
    """Percent of chip 0's busy time in the traced slice that the
    operations of ``component`` took (``unscoped``: those under none).
    None where there is no trace or no operation of it carries a
    component; 0.0 where some do and this one has none. The seven add up
    to 100."""
    scopes = of_cell(ctx)
    if not scopes or not scopes["marked"] or not scopes["busy_s"]:
        return None
    return 100.0 * scopes["by_component"][component] / scopes["busy_s"]


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) not in (1, 2):
        sys.exit("usage: python -m benchmarks.scope_reduce "
                 "<trace.xplane.pb> [rows to show]")
    shown = int(argv[1]) if len(argv) == 2 else _ROWS_SHOWN
    r = reduce_file(argv[0])
    if r is None:
        sys.exit("no device operation in this trace (a CPU run)")
    busy = r["busy_s"]
    print(f"busy {busy:.4f} s; programs with their HLO in the file "
          f"{r['programs'][0]}, without {r['programs'][1]}; named by a "
          f"consumer {100 * r['inherited_s'] / busy:.2f} %; reduced in "
          f"{r['reduce_s']:.1f} s")
    if not r["marked"]:
        print("no operation carries a component (a program from before "
              "the scopes)")
    print("busy by component (share of busy)")
    for name, sec in sorted(r["by_component"].items(), key=lambda kv: -kv[1]):
        print(f"  {sec:10.4f} s  {100 * sec / busy:6.2f} %  {name}")
    print("busy by component, pass, program, sublayer and operation")
    ranked = sorted(r["rows"].items(), key=lambda kv: -kv[1])
    for (component, which, program, sublayer, op), sec in ranked[:shown]:
        print(f"  {sec:10.4f} s  {100 * sec / busy:6.2f} %  "
              f"{component:9s} {which:9s} {program}  {sublayer}  [{op}]")
    rest = sum(sec for _k, sec in ranked[shown:])
    if rest:
        print(f"  {rest:10.4f} s  {100 * rest / busy:6.2f} %  "
              f"({len(ranked) - shown} more rows)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
