"""Selective-scan kernels of a state-space (Mamba-1) mixer (Pallas).

The recurrence of one layer, for every channel ``e`` of ``E`` and state
``n`` of ``N``::

    h_t[n, e] = exp(delta_t[e] * A[n, e]) * h_{t-1}[n, e]
                + delta_t[e] * c_t[e] * B_t[n]
    y_t[e]    = sum_n h_t[n, e] * C_t[n] + D[e] * c_t[e]

Everywhere here the state is laid out ``[N, E]``: the E channels on the
lanes and the N = 16 states on the sublanes are whole (8, 128) tiles with
nothing padded, where ``[E, N]`` would pad 16 to 128 lanes and take eight
times the memory. ``A`` goes in transposed the same way.

Two calls, each one device operation under its own name:

- :func:`selective_scan` (``ssm_scan``), the prefill: one sequence of S
  positions in chunks. The grid is (E-tiles, chunks), the E-tiles
  parallel and the chunks in order; ``h`` [N, E-tile] lives in the
  output block, which stays in VMEM across the chunks of a tile. A
  chunk's ``delta = softplus(.)`` and ``delta * c`` are formed at once
  into scratch, then the positions run in order, eight unrolled at a
  time: ``exp(delta A)`` and the outer product exist only in registers
  and nothing of size [S, N, E] is ever written. Positions at or past
  ``true_len`` get ``delta = 0``: ``exp(0) * h + 0`` leaves ``h`` as it
  was, so a padded bucket ends in the state of the true length.
- :func:`state_update` (``ssm_update``), the decode step: every slot's
  ``h`` read and written once, in place in the cache's stacked
  ``[layers, slots, N, E]`` array (aliased: the layers the call does not
  touch stay as they are), an inactive slot written back unchanged.

``B_t`` and ``C_t`` go in as ``[.., N, 1]`` columns so that a step
broadcasts them along the lanes without a relayout.

Each has a plain ``jax.numpy`` route (``*_plain``): the fallback where
the kernel is not taken (``inference.paged.kernel_route``), and the
tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...profiler import metrics as _metrics
from .flash_attention import _interpret

__all__ = ["selective_scan", "selective_scan_plain", "state_update",
           "state_update_plain", "selective_scan_routed",
           "state_update_routed"]

# route each took, counted where the call is traced (one movement a
# compiled layer, as ``serving.kernel.pallas``)
_SCAN_PALLAS = _metrics.counter("serving.kernel.ssm_scan.pallas")
_SCAN_PLAIN = _metrics.counter("serving.kernel.ssm_scan.plain")
_UPDATE_PALLAS = _metrics.counter("serving.kernel.ssm_update.pallas")
_UPDATE_PLAIN = _metrics.counter("serving.kernel.ssm_update.plain")

# typed literals: under jax_enable_x64 a bare python number traces as a
# weak 64-bit constant that Mosaic cannot legalize
_I0 = np.int32(0)
_F0 = np.float32(0.0)
_F1 = np.float32(1.0)
_F20 = np.float32(20.0)
_UNROLL = 8            # positions a loop iteration runs: one f32 tile
_VMEM_LIMIT = 48 * 1024 * 1024


def _softplus(x):
    """log(1 + e^x) in float32, the identity past 20 (as torch's)."""
    return jnp.where(x > _F20, x,
                     jnp.log(_F1 + jnp.exp(jnp.minimum(x, _F20))))


def _tile(n, picks):
    """The first of ``picks`` that divides ``n``, else ``n`` whole."""
    return next((p for p in picks if n % p == 0), n)


# ---------------------------------------------------------------------------
# prefill: the chunked scan of one sequence
# ---------------------------------------------------------------------------

def selective_scan_plain(dt_pre, c, b, cm, a_t, d_skip, h0, true_len):
    """The recurrence a position a step (``lax.scan``). ``dt_pre``, ``c``
    [S, E]; ``b``, ``cm`` [S, N]; ``a_t`` [N, E] and ``h0`` [N, E]
    float32; ``d_skip`` [E]; ``true_len`` an int32 scalar. Returns
    (y [S, E] float32, h [N, E] float32 after position ``true_len - 1``)."""
    f32 = jnp.float32
    s = dt_pre.shape[0]
    live = jnp.arange(s, dtype=jnp.int32) < true_len
    delta = jnp.where(live[:, None], _softplus(dt_pre.astype(f32)), _F0)
    cf = c.astype(f32)
    a_t = a_t.astype(f32)

    def step(h, inp):
        dt, x, bt, ct = inp
        h = jnp.exp(dt[None, :] * a_t) * h + x[None, :] * bt[:, None]
        return h, jnp.sum(h * ct[:, None], axis=0)

    h, y = jax.lax.scan(step, h0.astype(f32),
                        (delta, delta * cf, b.astype(f32), cm.astype(f32)))
    return y + d_skip.astype(f32)[None, :] * cf, h


def _scan_kernel(len_ref, dt_ref, c_ref, b_ref, cm_ref, a_ref, d_ref,
                 h0_ref, y_ref, h_ref, dt_scr, x_scr, y_scr, *, tc):
    f32 = jnp.float32
    chunk = pl.program_id(1)

    @pl.when(chunk == _I0)
    def _first():
        h_ref[...] = h0_ref[...]

    pos = chunk * np.int32(tc) + jax.lax.broadcasted_iota(
        jnp.int32, (tc, 1), 0)
    cf = c_ref[...].astype(f32)
    dt = jnp.where(pos < len_ref[0], _softplus(dt_ref[...].astype(f32)),
                   _F0)
    dt_scr[...] = dt
    x_scr[...] = dt * cf
    a = a_ref[...]

    def group(_, carry):
        # the position rides the carry: with concrete bounds the loop's
        # own index is a weak 64-bit integer under jax_enable_x64
        base, h = carry
        base = pl.multiple_of(base, _UNROLL)
        dt8 = dt_scr[pl.ds(base, _UNROLL), :]
        x8 = x_scr[pl.ds(base, _UNROLL), :]
        rows = []
        for j in range(_UNROLL):
            t = base + np.int32(j)
            h = jnp.exp(dt8[j:j + 1, :] * a) * h + x8[j:j + 1, :] * b_ref[t]
            rows.append(jnp.sum(h * cm_ref[t], axis=0, keepdims=True))
        y_scr[pl.ds(base, _UNROLL), :] = jnp.concatenate(rows, axis=0)
        return base + np.int32(_UNROLL), h

    h_ref[...] = jax.lax.fori_loop(0, tc // _UNROLL, group,
                                   (_I0, h_ref[...]))[1]
    y_ref[...] = (y_scr[...] + d_ref[...] * cf).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "name"))
def selective_scan(dt_pre, c, b, cm, a_t, d_skip, h0, true_len,
                   interpret=None, name="ssm_scan"):
    """:func:`selective_scan_plain` as one Pallas call. S is a multiple
    of 8 (the caller pads to a bucket); y comes back in ``c``'s dtype."""
    f32 = jnp.float32
    s, e = dt_pre.shape
    n = a_t.shape[0]
    if s % _UNROLL:
        raise ValueError(f"selective_scan: {s} positions are not a "
                         f"multiple of {_UNROLL}")
    tc = _tile(s, (128,))
    te = _tile(e, (512, 256, 128))
    if interpret is None:
        interpret = _interpret()
    seq = pl.BlockSpec((tc, te), lambda ei, ci, ln: (ci, ei))
    col = pl.BlockSpec((tc, n, 1), lambda ei, ci, ln: (ci, _I0, _I0))
    per_e = pl.BlockSpec((n, te), lambda ei, ci, ln: (_I0, ei))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e // te, s // tc),
        in_specs=[seq, seq, col, col, per_e,
                  pl.BlockSpec((1, te), lambda ei, ci, ln: (_I0, ei)),
                  per_e],
        out_specs=[seq, per_e],
        scratch_shapes=[pltpu.VMEM((tc, te), f32)] * 3)
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, tc=tc),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, e), c.dtype),
                   jax.ShapeDtypeStruct((n, e), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(jnp.asarray(true_len, jnp.int32).reshape(1), dt_pre, c,
      b.astype(f32)[:, :, None], cm.astype(f32)[:, :, None],
      a_t.astype(f32), d_skip.astype(f32)[None, :], h0.astype(f32))
    return y, h


# ---------------------------------------------------------------------------
# decode: one step of every slot, in place in the stacked state
# ---------------------------------------------------------------------------

def state_update_plain(states, layer, dt, c, b, cm, a_t, d_skip, active):
    """One step of layer ``layer`` for every slot. ``states``
    [layers, B, N, E] float32; ``dt`` (already softplus'd) and ``c``
    [B, E]; ``b``, ``cm`` [B, N]; ``active`` [B] bool. Returns (states
    with the layer's rows of the active slots stepped, y [B, E]
    float32)."""
    f32 = jnp.float32
    h = states[layer]
    dt, cf = dt.astype(f32), c.astype(f32)
    hn = jnp.exp(dt[:, None, :] * a_t.astype(f32)[None]) * h \
        + (dt * cf)[:, None, :] * b.astype(f32)[:, :, None]
    hn = jnp.where(active[:, None, None], hn, h)
    y = jnp.sum(hn * cm.astype(f32)[:, :, None], axis=1) \
        + d_skip.astype(f32)[None, :] * cf
    return states.at[layer].set(hn), y


def _update_kernel(act_ref, dt_ref, c_ref, b_ref, cm_ref, a_ref, d_ref,
                   h_ref, o_ref, y_ref, *, bt):
    a = a_ref[...]
    dt = dt_ref[...]
    c = c_ref[...]
    first = pl.program_id(0) * np.int32(bt)
    rows = []
    for j in range(bt):
        h = h_ref[0, j]
        dtj = dt[j:j + 1, :]
        hn = jnp.exp(dtj * a) * h + (dtj * c[j:j + 1, :]) * b_ref[j]
        hn = jnp.where(act_ref[first + np.int32(j)] > _I0, hn, h)
        o_ref[0, j] = hn
        rows.append(jnp.sum(hn * cm_ref[j], axis=0, keepdims=True))
    y_ref[...] = jnp.concatenate(rows, axis=0) + d_ref[...] * c


@functools.partial(jax.jit, static_argnames=("layer", "interpret", "name"))
def state_update(states, layer, dt, c, b, cm, a_t, d_skip, active,
                 interpret=None, name="ssm_update"):
    """:func:`state_update_plain` as one Pallas call whose output is the
    ``states`` buffer itself (aliased), so that a program that was
    handed the state donated steps it in place."""
    f32 = jnp.float32
    layers, nb, n, e = states.shape
    bt = _tile(nb, (8,))
    te = _tile(e, (1280, 1024, 512, 256, 128))
    if interpret is None:
        interpret = _interpret()
    layer = np.int32(layer)
    row = pl.BlockSpec((bt, te), lambda bi, ei, act: (bi, ei))
    col = pl.BlockSpec((bt, n, 1), lambda bi, ei, act: (bi, _I0, _I0))
    per_e = pl.BlockSpec((n, te), lambda bi, ei, act: (_I0, ei))
    state = pl.BlockSpec((1, bt, n, te),
                         lambda bi, ei, act: (layer, bi, _I0, ei))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb // bt, e // te),
        in_specs=[row, row, col, col, per_e,
                  pl.BlockSpec((1, te), lambda bi, ei, act: (_I0, ei)),
                  state],
        out_specs=[state, row])
    new, y = pl.pallas_call(
        functools.partial(_update_kernel, bt=bt),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(states.shape, f32),
                   jax.ShapeDtypeStruct((nb, e), f32)],
        # operand 7 (the scalar-prefetched ``active`` counts) is the state
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(active.astype(jnp.int32), dt.astype(f32), c.astype(f32),
      b.astype(f32)[:, :, None], cm.astype(f32)[:, :, None],
      a_t.astype(f32), d_skip.astype(f32)[None, :], states)
    return new, y


# ---------------------------------------------------------------------------
# routing (``inference.paged.kernel_route``, as attention's)
# ---------------------------------------------------------------------------

def _takes_kernel(kernel_mode):
    from ...inference.paged import kernel_route
    return kernel_route(kernel_mode) != "dense"


def selective_scan_routed(*args, kernel_mode=None):
    """The scan by the route ``kernel_mode`` picks: the kernel on a TPU
    and where ``pallas`` is forced (interpreted on the CPU), the plain
    scan otherwise; y in ``c``'s dtype either way."""
    if _takes_kernel(kernel_mode):
        _SCAN_PALLAS.inc()
        return selective_scan(*args)
    _SCAN_PLAIN.inc()
    y, h = selective_scan_plain(*args)
    return y.astype(args[1].dtype), h


def state_update_routed(states, layer, *args, kernel_mode=None):
    """The decode step's state update by the same rule."""
    if _takes_kernel(kernel_mode):
        _UPDATE_PALLAS.inc()
        return state_update(states, layer, *args)
    _UPDATE_PLAIN.inc()
    return state_update_plain(states, layer, *args)
