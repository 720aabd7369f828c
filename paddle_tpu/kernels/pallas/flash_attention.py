"""Original TPU flash-attention kernels (Pallas): fwd + bwd, native GQA,
varlen.

Capability parity with the reference's FA2 integration
(`paddle/phi/kernels/gpu/flash_attn_kernel.cu:128` — `flash_attn_fwd` and
`flash_attn_varlen_fwd` dynload into the vendored flashattn library, GQA via
`num_heads_k != num_heads`). On TPU the "vendor kernel" seam is Pallas; these
kernels are written for the MXU rather than translated from the CUDA library:

- **Native GQA**: q is laid out [batch, kv_head, group, seq, dim] and the
  `group` axis is folded into the matmul row dimension, so each KV block is
  fetched from HBM once per *group* (not once per query head) and KV is never
  materialized expanded. The group fold also makes the MXU operand taller
  (group*block_q rows), improving systolic-array utilization at small
  block_q.
- **Online softmax** with running (m, l) in VMEM scratch across the KV grid
  dimension; output and per-row logsumexp L are written on the last KV step.
  L is the only extra residual the backward needs.
- **Backward** recomputes P = exp(s - L) blockwise (flash-attention-2 style:
  no dP materialization in HBM): a dq kernel (grid over q blocks, accumulate
  over kv blocks) and a fused dk/dv kernel (grid over kv blocks, accumulate
  over q blocks — the GQA group fold makes the sum over grouped query heads
  implicit in the matmul reduction).
- **Varlen / ragged batches** via segment ids + intra-segment positions
  (the TPU-native encoding of `cu_seqlens`): tokens attend only within equal
  segment ids; causal masking compares intra-segment positions. The packed
  `flash_attn_varlen` entry point converts `cu_seqlens` to segments.
- Causal runs skip fully-masked blocks (predicated on grid position).

Tested against the dense-softmax oracle (tests/kernels/
test_flash_attention.py) in interpret mode on CPU; compiled on TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "flash_attention", "flash_attn_varlen", "flash_attention_fwd",
]

# f32-typed constants: under jax_enable_x64 a bare Python float traces as a
# weak f64 constant, and Mosaic cannot legalize the resulting f64->f32 truncf
# inside a TPU kernel — every in-kernel literal must be explicitly f32.
_NEG = np.float32(-1e30)  # large-negative logit for masked entries
_BIG = np.float32(1e30)   # lse sentinel for fully-masked rows -> P == 0
_ZERO = np.float32(0.0)
_I0 = np.int32(0)   # index-map literal (i64 under x64 breaks Mosaic)
_ONE = np.float32(1.0)


def _interpret() -> bool:
    import os
    if os.environ.get("PADDLE_PALLAS_FORCE_COMPILE"):
        # cross-lowering gate (tools/tpu_lowering_gate.py): run the real
        # Mosaic pipeline even on a CPU host so legalization is proven
        return False
    return jax.default_backend() == "cpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# runtime block-size autotune (paddle.incubate.autotune.set_config
# {"kernel": {"enable": True}} turns it on — the reference's exhaustive
# kernel search, applied to the Pallas grid): first call per shape times
# the candidate grid on-device and caches the winner.
_AUTOTUNE = {"enable": False, "cache": {}}


def _tune_file():
    import os
    pkg = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))  # .../paddle_tpu
    return os.environ.get(
        "PADDLE_TPU_AUTOTUNE_CACHE",
        os.path.join(os.path.dirname(pkg), ".pallas_autotune.json"))


def _device_kind():
    return jax.devices()[0].device_kind.lower()


def _tune_cache_load(tkey):
    """File-backed sweep results: a chip belongs to one process at a
    time, so an in-memory cache makes every process re-pay the on-chip
    sweep. Keyed by device kind — a v5e winner means nothing on another
    generation."""
    import json
    import os
    path = _tune_file()
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            data = json.load(f)
        hit = data.get(_device_kind(), {}).get(repr(tkey))
        return tuple(hit) if hit else None
    except (OSError, ValueError):
        return None


def _tune_cache_store(tkey, blocks):
    import fcntl
    import json
    import os
    path = _tune_file()
    try:
        with open(path + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            try:
                with open(path) as f:
                    data = json.load(f)
            except (OSError, ValueError):
                data = {}
            data.setdefault(_device_kind(), {})[repr(tkey)] = list(blocks)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(data, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
    except OSError:
        pass

_SWEEP_BQ = (128, 256, 512, 1024)
_SWEEP_BK = (256, 512, 1024)


_SWEEP_ITERS = 20


def _sweep_blocks(q, k, v, causal, scale, sq, sk, group):
    """Two-stage candidate search. Timing method: each candidate is ONE
    jitted lax.scan of _SWEEP_ITERS serialized kernel calls ending in a
    scalar, so host dispatch is paid once per candidate instead of per
    iteration and the timing is device time.

    Stage 1 ranks all candidates on forward time; stage 2 re-times the
    top 3 with forward+backward (the dq/dkv kernels REUSE the tuned
    blocks, and in training the backward is ~2/3 of the attention
    cost), picking the total-time winner."""
    import time as _time

    from jax import lax

    def timed(bq, bk, with_bwd):
        def one(q_, k_, v_):
            return flash_attention(q_, k_, v_, causal=causal, scale=scale,
                                   block_q=bq, block_k=bk)

        if with_bwd:
            g = jax.grad(
                lambda q_, k_, v_: one(q_, k_, v_).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2))

            @jax.jit
            def run(q_, k_, v_):
                def body(carry, _):
                    c, acc = carry
                    dq, dk, dv = g(c, k_, v_)
                    acc = (acc + dk.astype(jnp.float32).sum()
                           + dv.astype(jnp.float32).sum())
                    return (c + 1e-3 * dq.astype(c.dtype), acc), ()
                (cf, accf), _ = lax.scan(
                    body, (q_, jnp.float32(0)), None,
                    length=_SWEEP_ITERS)
                return cf[(0,) * cf.ndim].astype(jnp.float32) + accf
        else:
            @jax.jit
            def run(q_, k_, v_):
                def body(c, _):
                    return one(c, k_, v_).astype(c.dtype), ()
                out, _ = lax.scan(body, q_, None, length=_SWEEP_ITERS)
                return out[(0,) * out.ndim].astype(jnp.float32)

        float(run(q, k, v))  # compile + warm; host fetch of the scalar
        best = float("inf")
        for _ in range(2):
            t0 = _time.perf_counter()
            float(run(q, k, v))
            best = min(best, _time.perf_counter() - t0)
        return best

    ranked = []
    for bq in _SWEEP_BQ:
        if bq > _round_up(sq, 128):
            continue
        for bk in _SWEEP_BK:
            if bk > _round_up(sk, 128):
                continue
            try:
                ranked.append((timed(bq, bk, False), (bq, bk)))
            except Exception:  # noqa: BLE001 — e.g. VMEM overflow
                continue
    if not ranked:
        return default_block_sizes(sq, sk, group)
    ranked.sort(key=lambda e: e[0])
    best, best_t = None, float("inf")
    for _, cand in ranked[:3]:
        try:
            dt = timed(*cand, True)
        except Exception:  # noqa: BLE001
            continue
        if dt < best_t:
            best, best_t = cand, dt
    # every fwd+bwd re-timing failed (e.g. the dq/dkv kernels overflow
    # VMEM at all fwd-ranked blocks): the defaults are sized for the
    # backward too — never return a config whose backward just crashed
    return best or default_block_sizes(sq, sk, group)


def default_block_sizes(sq: int, sk: int, group: int):
    """Per-shape block table (swept on v5e; see BASELINE.md kernel notes).
    Rows of the q operand are group*block_q, so larger GQA groups take a
    smaller block_q to keep the operand within VMEM."""
    if group >= 8:
        bq = 128
    elif group >= 2:
        bq = 256
    else:
        bq = 512
    bk = 512
    return min(bq, _round_up(sq, 128)), min(bk, _round_up(sk, 128))


# ---------------------------------------------------------------------------
# masking helper (shared by fwd and both bwd kernels)
# ---------------------------------------------------------------------------

def _block_mask(i, j, bq, bk, sk, causal, off, has_seg, qseg, kseg, qpos,
                kpos):
    """(bq, bk) bool mask for q block i vs kv block j.

    Without segments, positions are global (block index * block size + iota)
    and padded kv columns (>= true sk) are invalid; causal masking is
    bottom-right aligned (`off = sk - sq`), matching FA2/paddle semantics
    for cross seqlens — a decode query attends the whole prefix. With
    segments, validity is segment equality and causality uses intra-segment
    positions (padding carries segment id -1 for kv / -2 for q so it never
    matches).
    """
    if has_seg:
        valid = qseg[:, None] == kseg[None, :]
        if causal:
            valid &= qpos[:, None] >= kpos[None, :]
        return valid
    kv_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kv_idx < sk
    if causal:
        q_idx = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        valid &= (q_idx + off) >= kv_idx
    return valid


def _expand_rows(mask_2d, group, rows):
    """(bq, bk) -> (group*bq, bk): every query head in the group sees the
    same positions, so the mask is replicated along the folded group axis."""
    bq, bk = mask_2d.shape
    return jnp.broadcast_to(mask_2d[None], (group, bq, bk)).reshape(rows, bk)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, group, bq, bk, nk, sk, off, scale, causal,
                has_seg):
    if has_seg:
        (qseg_ref, kseg_ref, qpos_ref, kpos_ref,
         q_ref, k_ref, v_ref, o_ref, l_ref, acc, m_scr, l_scr) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, l_ref, acc, m_scr, l_scr) = refs
    i = pl.program_id(2)
    j = pl.program_id(3)
    rows = group * bq

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)

    def _body():
        q = q_ref[0, 0].reshape(rows, q_ref.shape[-1])
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if has_seg:
            mask2 = _block_mask(i, j, bq, bk, sk, causal, off, True,
                                qseg_ref[0], kseg_ref[0],
                                qpos_ref[0], kpos_ref[0])
        else:
            mask2 = _block_mask(i, j, bq, bk, sk, causal, off, False,
                                None, None, None, None)
        mask = _expand_rows(mask2, group, rows)
        s = jnp.where(mask, s, _NEG)

        m_prev = m_scr[:, :1]                        # (rows, 1)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # explicit zero for masked entries: when a whole row is masked so
        # far, exp(s - m) would be 1, not 0
        p = jnp.where(mask, jnp.exp(s - m_new), _ZERO)
        alpha = jnp.exp(m_prev - m_new)              # (rows, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0, 0],
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # causal block skip: a block fully above the diagonal does no work
    if causal and not has_seg:
        pl.when((i + 1) * bq - 1 + off >= j * bk)(_body)
    else:
        _body()

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        m = m_scr[:, :1]
        safe_l = jnp.where(l > _ZERO, l, _ONE)
        o = (acc[:] / safe_l).astype(o_ref.dtype)
        o_ref[0, 0] = o.reshape(o_ref.shape[2:])
        lse = jnp.where(l > _ZERO, m + jnp.log(safe_l), _BIG)
        l_ref[0, 0] = lse.reshape(group, bq, 1)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _recompute_p(q, k, lse, mask, scale):
    """P = softmax block recomputed from the saved logsumexp (already
    normalized: p = exp(s - L))."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, _NEG)
    return jnp.where(mask, jnp.exp(s - lse), _ZERO)


def _dq_kernel(*refs, group, bq, bk, nk, sk, off, scale, causal,
               has_seg):
    if has_seg:
        (qseg_ref, kseg_ref, qpos_ref, kpos_ref,
         q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_ref, dq_acc) = refs
    i = pl.program_id(2)
    j = pl.program_id(3)
    rows = group * bq

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _body():
        dp_dim = q_ref.shape[-1]
        q = q_ref[0, 0].reshape(rows, dp_dim)
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].reshape(rows, dp_dim)
        lse = l_ref[0, 0].reshape(rows, 1)
        delta = d_ref[0, 0].reshape(rows, 1)
        if has_seg:
            mask2 = _block_mask(i, j, bq, bk, sk, causal, off, True,
                                qseg_ref[0], kseg_ref[0],
                                qpos_ref[0], kpos_ref[0])
        else:
            mask2 = _block_mask(i, j, bq, bk, sk, causal, off, False,
                                None, None, None, None)
        mask = _expand_rows(mask2, group, rows)
        p = _recompute_p(q, k, lse, mask, scale)
        dp = jax.lax.dot_general(do.astype(v.dtype), v,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot(ds.astype(k.dtype), k,
                                 preferred_element_type=jnp.float32)

    if causal and not has_seg:
        pl.when((i + 1) * bq - 1 + off >= j * bk)(_body)
    else:
        _body()

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype).reshape(
            dq_ref.shape[2:])


def _dkv_kernel(*refs, group, bq, bk, nq, sk, off, scale, causal,
                has_seg):
    # grid is (batch, kv_head, kv_block, q_block): accumulate over q blocks
    if has_seg:
        (qseg_ref, kseg_ref, qpos_ref, kpos_ref, q_ref, k_ref, v_ref,
         do_ref, l_ref, d_ref, dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, l_ref, d_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    j = pl.program_id(2)   # kv block
    i = pl.program_id(3)   # q block
    rows = group * bq

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _body():
        dp_dim = q_ref.shape[-1]
        q = q_ref[0, 0].reshape(rows, dp_dim)
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].reshape(rows, dp_dim)
        lse = l_ref[0, 0].reshape(rows, 1)
        delta = d_ref[0, 0].reshape(rows, 1)
        if has_seg:
            mask2 = _block_mask(i, j, bq, bk, sk, causal, off, True,
                                qseg_ref[0], kseg_ref[0],
                                qpos_ref[0], kpos_ref[0])
        else:
            mask2 = _block_mask(i, j, bq, bk, sk, causal, off, False,
                                None, None, None, None)
        mask = _expand_rows(mask2, group, rows)
        p = _recompute_p(q, k, lse, mask, scale)
        # dv += P^T dO  — the matmul reduction over `rows` sums over the
        # GQA group, which is exactly the grouped-head gradient sum
        pt = p.astype(do.dtype)
        dv_acc[:] += jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do.astype(v.dtype), v,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal and not has_seg:
        pl.when((i + 1) * bq - 1 + off >= j * bk)(_body)
    else:
        _body()

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _seg_specs(bq, bk):
    """BlockSpecs for (q_seg, kv_seg, q_pos, kv_pos): [B, S] int32."""
    return [
        pl.BlockSpec((1, bq), lambda b, h, i, j: (b, i)),
        pl.BlockSpec((1, bk), lambda b, h, i, j: (b, j)),
        pl.BlockSpec((1, bq), lambda b, h, i, j: (b, i)),
        pl.BlockSpec((1, bk), lambda b, h, i, j: (b, j)),
    ]


def _seg_specs_kvmajor(bq, bk):
    # grid (b, h, kv_block j, q_block i)
    return [
        pl.BlockSpec((1, bq), lambda b, h, j, i: (b, i)),
        pl.BlockSpec((1, bk), lambda b, h, j, i: (b, j)),
        pl.BlockSpec((1, bq), lambda b, h, j, i: (b, i)),
        pl.BlockSpec((1, bk), lambda b, h, j, i: (b, j)),
    ]


def _sem(n):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3 + ("arbitrary",) * (n - 3))


@functools.lru_cache(maxsize=64)
def _make_flash(causal, scale, bq, bk, has_seg, sk_true, off):
    """Build the custom-vjp flash attention for static (causal, scale,
    blocks, segments?) so jax caches one callable per configuration.

    Operates on the GQA-native internal layout:
      q5 [B, Hk, G, Sqp, Dp], k4/v4 [B, Hk, Skp, Dp] (padded), optional
      seg/pos arrays [B, Sqp]/[B, Skp] (int32).
    Returns (out5, lse [B, Hk, G, Sqp] f32).
    """

    def fwd_core(*args):
        # args: [qseg, kseg, qpos, kpos,] q5, k4, v4  (pallas order)
        q5, k4, v4 = args[-3:]
        B, Hk, G, Sq, Dp = q5.shape
        Sk = k4.shape[2]
        nq, nk = Sq // bq, Sk // bk
        rows = G * bq
        kernel = functools.partial(
            _fwd_kernel, group=G, bq=bq, bk=bk, nk=nk, sk=sk_true,
            off=off, scale=np.float32(scale), causal=causal,
            has_seg=has_seg)
        in_specs = (_seg_specs(bq, bk) if has_seg else []) + [
            pl.BlockSpec((1, 1, G, bq, Dp), lambda b, h, i, j: (b, h, _I0, i, _I0)),
            pl.BlockSpec((1, 1, bk, Dp), lambda b, h, i, j: (b, h, j, _I0)),
            pl.BlockSpec((1, 1, bk, Dp), lambda b, h, i, j: (b, h, j, _I0)),
        ]
        out, lse = pl.pallas_call(
            kernel,
            grid=(B, Hk, nq, nk),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, G, bq, Dp),
                             lambda b, h, i, j: (b, h, _I0, i, _I0)),
                pl.BlockSpec((1, 1, G, bq, 1),
                             lambda b, h, i, j: (b, h, _I0, i, _I0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(q5.shape, q5.dtype),
                jax.ShapeDtypeStruct((B, Hk, G, Sq, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((rows, Dp), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
            ],
            compiler_params=_sem(4),
            interpret=_interpret(),
            name="flash_fwd",
        )(*args)
        return out, lse

    def fwd_call(q5, k4, v4, qseg, kseg, qpos, kpos):
        args = ([qseg, kseg, qpos, kpos] if has_seg else []) + \
            [q5, k4, v4]
        return fwd_core(*args)

    @jax.custom_vjp
    def flash(q5, k4, v4, qseg, kseg, qpos, kpos):
        return fwd_call(q5, k4, v4, qseg, kseg, qpos, kpos)

    def flash_fwd(q5, k4, v4, qseg, kseg, qpos, kpos):
        out, lse = fwd_call(q5, k4, v4, qseg, kseg, qpos, kpos)
        return (out, lse), (q5, k4, v4, qseg, kseg, qpos, kpos, out, lse)

    def dq_core(*args):
        q5, k4, v4, do5, lse, delta = args[-6:]
        B, Hk, G, Sq, Dp = q5.shape
        Sk = k4.shape[2]
        nq, nk = Sq // bq, Sk // bk
        rows = G * bq
        common = dict(group=G, bq=bq, bk=bk, sk=sk_true, off=off,
                      scale=np.float32(scale), causal=causal,
                      has_seg=has_seg)
        q_spec = pl.BlockSpec((1, 1, G, bq, Dp),
                              lambda b, h, i, j: (b, h, _I0, i, _I0))
        kv_spec = pl.BlockSpec((1, 1, bk, Dp), lambda b, h, i, j: (b, h, j, _I0))
        lse_spec = pl.BlockSpec((1, 1, G, bq, 1),
                                lambda b, h, i, j: (b, h, _I0, i, _I0))
        return pl.pallas_call(
            functools.partial(_dq_kernel, nk=nk, **common),
            grid=(B, Hk, nq, nk),
            in_specs=(_seg_specs(bq, bk) if has_seg else [])
            + [q_spec, kv_spec, kv_spec, q_spec, lse_spec, lse_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(q5.shape, q5.dtype),
            scratch_shapes=[pltpu.VMEM((rows, Dp), jnp.float32)],
            compiler_params=_sem(4),
            interpret=_interpret(),
            name="flash_dq",
        )(*args)

    def dkv_core(*args):
        q5, k4, v4, do5, lse, delta = args[-6:]
        B, Hk, G, Sq, Dp = q5.shape
        Sk = k4.shape[2]
        nq, nk = Sq // bq, Sk // bk
        common = dict(group=G, bq=bq, bk=bk, sk=sk_true, off=off,
                      scale=np.float32(scale), causal=causal,
                      has_seg=has_seg)
        # kv-major grid for dk/dv
        q_spec2 = pl.BlockSpec((1, 1, G, bq, Dp),
                               lambda b, h, j, i: (b, h, _I0, i, _I0))
        kv_spec2 = pl.BlockSpec((1, 1, bk, Dp),
                                lambda b, h, j, i: (b, h, j, _I0))
        lse_spec2 = pl.BlockSpec((1, 1, G, bq, 1),
                                 lambda b, h, j, i: (b, h, _I0, i, _I0))
        return pl.pallas_call(
            functools.partial(_dkv_kernel, nq=nq, **common),
            grid=(B, Hk, nk, nq),
            in_specs=(_seg_specs_kvmajor(bq, bk) if has_seg else [])
            + [q_spec2, kv_spec2, kv_spec2, q_spec2, lse_spec2, lse_spec2],
            out_specs=[kv_spec2, kv_spec2],
            out_shape=[
                jax.ShapeDtypeStruct(k4.shape, k4.dtype),
                jax.ShapeDtypeStruct(v4.shape, v4.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, Dp), jnp.float32),
                pltpu.VMEM((bk, Dp), jnp.float32),
            ],
            compiler_params=_sem(4),
            interpret=_interpret(),
            name="flash_dkv",
        )(*args)

    def flash_bwd(res, cts):
        q5, k4, v4, qseg, kseg, qpos, kpos, out, lse = res
        do5, dlse = cts
        do5 = do5.astype(q5.dtype)
        # delta = rowsum(dO * O), f32, same layout as lse. A cotangent on
        # the lse output folds straight in: dL/ds_ij picks up
        # glse_i * p_ij, and the kernels compute ds = p * (dp - delta),
        # so delta_eff = delta - glse carries it with no kernel change.
        delta = jnp.sum(do5.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)
        if dlse is not None:
            delta = delta - dlse.astype(jnp.float32)

        seg_args = [qseg, kseg, qpos, kpos] if has_seg else []
        dq = dq_core(*seg_args, q5, k4, v4, do5, lse, delta)
        dk, dv = dkv_core(*seg_args, q5, k4, v4, do5, lse, delta)
        if has_seg:
            # integer inputs take float0 cotangents
            zct = lambda x: np.zeros(x.shape, jax.dtypes.float0)
            zeros = (zct(qseg), zct(kseg), zct(qpos), zct(kpos))
        else:
            zeros = (None, None, None, None)
        return (dq, dk, dv) + zeros

    flash.defvjp(flash_fwd, flash_bwd)
    return flash


# ---------------------------------------------------------------------------
# public entry points ([B, S, H, D] paddle layout)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, causal=False, scale=None,
                    q_segment_ids=None, kv_segment_ids=None,
                    q_positions=None, kv_positions=None,
                    block_q=None, block_k=None, return_lse=False,
                    on_mesh=None):
    """Flash attention on [B, Sq, Hq, D] / [B, Sk, Hk, D] arrays with
    Hq = group * Hk (native GQA — KV heads are NOT expanded). Segment ids
    (with optional intra-segment positions) give varlen/ragged semantics.
    Differentiable (custom VJP runs the Pallas dq and dk/dv kernels).

    ``on_mesh`` (``kernels.on_mesh.current()``: a mesh and its batch axes)
    runs the kernels under ``jax.shard_map``, each device on its own
    batch rows and KV-head groups — no collective, softmax rows never
    cross a shard. Without it the call is local to one device."""
    if on_mesh is None:
        return _flash_local(q, k, v, causal, scale, q_segment_ids,
                            kv_segment_ids, q_positions, kv_positions,
                            block_q, block_k, return_lse)
    from jax.sharding import PartitionSpec as P

    from ..on_mesh import batch_head_axes

    b_ax, h_ax = batch_head_axes(on_mesh, q.shape[0], k.shape[2])
    qkv = P(b_ax, None, h_ax, None)
    # the optional [B, S] id arrays: an absent one is an empty pytree
    ids = (q_segment_ids, kv_segment_ids, q_positions, kv_positions)

    def local(q_, k_, v_, *ids_):
        return _flash_local(q_, k_, v_, causal, scale, *ids_, block_q,
                            block_k, return_lse)

    return jax.shard_map(
        local, mesh=on_mesh[0],
        in_specs=(qkv, qkv, qkv) + tuple(
            None if a is None else P(b_ax, None) for a in ids),
        out_specs=(qkv, P(b_ax, h_ax, None)) if return_lse else qkv,
        check_vma=False,
    )(q, k, v, *ids)


def _flash_local(q, k, v, causal, scale, q_segment_ids, kv_segment_ids,
                 q_positions, kv_positions, block_q, block_k, return_lse):
    """:func:`flash_attention` on one device's operands."""
    B, Sq, Hq, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    if Hq % Hk != 0:
        raise ValueError(f"query heads {Hq} not a multiple of kv heads {Hk}")
    G = Hq // Hk
    sm_scale = float(scale if scale is not None else 1.0 / math.sqrt(D))

    has_seg = q_segment_ids is not None
    bq, bk = default_block_sizes(Sq, Sk, G)
    if _AUTOTUNE["enable"] and block_q is None and block_k is None \
            and not has_seg and not _interpret():
        tkey = (B, Sq, Sk, Hq, Hk, D, causal, str(q.dtype))
        tuned = _AUTOTUNE["cache"].get(tkey)
        if tuned is None:
            tuned = _tune_cache_load(tkey)
            if tuned is not None:
                _AUTOTUNE["cache"][tkey] = tuned
        if tuned is None and not isinstance(q, jax.core.Tracer):
            # sweep only on concrete arrays — under a jit trace the
            # timings are meaningless and caching here would pin the
            # defaults for this shape forever
            tuned = _sweep_blocks(q, k, v, causal, scale, Sq, Sk, G)
            _AUTOTUNE["cache"][tkey] = tuned
            _tune_cache_store(tkey, tuned)
        if tuned is not None:
            bq, bk = tuned
    if block_q:
        bq = min(block_q, _round_up(Sq, 128))
    if block_k:
        bk = min(block_k, _round_up(Sk, 128))

    Sqp, Skp = _round_up(Sq, bq), _round_up(Sk, bk)
    Dp = _round_up(D, 128)

    # [B, S, H, D] -> [B, Hk, G, S, D] (+ pad seq to block, head dim to 128)
    q5 = q.reshape(B, Sq, Hk, G, D).transpose(0, 2, 3, 1, 4)
    q5 = jnp.pad(q5, ((0, 0), (0, 0), (0, 0), (0, Sqp - Sq), (0, Dp - D)))
    k4 = jnp.pad(k.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, Skp - Sk), (0, Dp - D)))
    v4 = jnp.pad(v.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, Skp - Sk), (0, Dp - D)))

    if has_seg:
        if kv_segment_ids is None:
            kv_segment_ids = q_segment_ids
        qseg = jnp.pad(q_segment_ids.astype(jnp.int32),
                       ((0, 0), (0, Sqp - Sq)), constant_values=-2)
        kseg = jnp.pad(kv_segment_ids.astype(jnp.int32),
                       ((0, 0), (0, Skp - Sk)), constant_values=-1)
        if q_positions is None:
            q_positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32),
                                           (B, Sq))
        if kv_positions is None:
            kv_positions = jnp.broadcast_to(jnp.arange(Sk, dtype=jnp.int32),
                                            (B, Sk))
        qpos = jnp.pad(q_positions.astype(jnp.int32), ((0, 0), (0, Sqp - Sq)))
        kpos = jnp.pad(kv_positions.astype(jnp.int32),
                       ((0, 0), (0, Skp - Sk)))
    else:
        qseg = kseg = qpos = kpos = None

    # bottom-right causal alignment (FA2/paddle): off = Sk - Sq
    flash = _make_flash(bool(causal), sm_scale, bq, bk, has_seg,
                        Sk, Sk - Sq)
    out5, lse = flash(q5, k4, v4, qseg, kseg, qpos, kpos)

    out = out5[:, :, :, :Sq, :D].transpose(0, 3, 1, 2, 4).reshape(
        B, Sq, Hq, D)
    if return_lse:
        # [B, Hk, G, Sqp, 1] -> [B, Hq, Sq]
        lse_out = lse[:, :, :, :Sq, 0].reshape(B, Hq, Sq)
        return out, lse_out
    return out


def flash_attn_varlen(q, k, v, cu_seqlens_q, cu_seqlens_k, causal=False,
                      scale=None, block_q=None, block_k=None):
    """Packed varlen attention (reference `flash_attn_varlen_fwd`,
    `flash_attn_kernel.cu:128`): q [Tq, Hq, D], k/v [Tk, Hk, D] with
    `cu_seqlens_*` [n+1] prefix sums. Sequences attend only within
    themselves; causal uses intra-sequence positions."""
    tq = q.shape[0]
    tk = k.shape[0]
    cu_q = cu_seqlens_q.astype(jnp.int32)
    cu_k = cu_seqlens_k.astype(jnp.int32)
    pos_q = jnp.arange(tq, dtype=jnp.int32)
    pos_k = jnp.arange(tk, dtype=jnp.int32)
    seg_q = jnp.searchsorted(cu_q, pos_q, side="right").astype(jnp.int32) - 1
    seg_k = jnp.searchsorted(cu_k, pos_k, side="right").astype(jnp.int32) - 1
    # bottom-right causal alignment per sequence (FA2 varlen semantics):
    # shift query positions by len_k - len_q so the last query lines up with
    # the last key even when the two sides have different lengths
    len_q = cu_q[seg_q + 1] - cu_q[seg_q]
    len_k_q = cu_k[jnp.minimum(seg_q + 1, cu_k.shape[0] - 1)] - \
        cu_k[jnp.minimum(seg_q, cu_k.shape[0] - 1)]
    rel_q = pos_q - cu_q[seg_q] + (len_k_q - len_q)
    rel_k = pos_k - cu_k[seg_k]
    out = flash_attention(
        q[None], k[None], v[None], causal=causal, scale=scale,
        q_segment_ids=seg_q[None], kv_segment_ids=seg_k[None],
        q_positions=rel_q[None], kv_positions=rel_k[None],
        block_q=block_q, block_k=block_k)
    return out[0]


def flash_attention_fwd(q, k, v, causal=False, scale=None):
    """Back-compat dense entry point ([B, S, H, D], KV may be grouped)."""
    return flash_attention(q, k, v, causal=causal, scale=scale)
