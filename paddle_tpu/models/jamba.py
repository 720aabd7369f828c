"""Jamba (``model_type`` ``jamba``): state-space (Mamba-1) layers with an
attention layer among every few, dense SwiGLU feed-forwards.

A block, everything in the parameters' dtype except where said::

    x += mixer(input_layernorm(x));  x += mlp(pre_ff_layernorm(x))

then a final RMSNorm and a tied head. Layer ``i`` is attention where
``i % attn_layer_period == attn_layer_offset`` and a state-space mixer
otherwise.

The state-space mixer, ``E = mamba_expand * hidden``, ``N`` states,
``R = mamba_dt_rank``, ``K = mamba_d_conv``, for a sequence ``x[1..T]``::

    [u, z] = x W_in
    c_t = silu(b_conv + sum_{j<K} w_conv[j] * u_{t-K+1+j})   zeros before t=1
    [r, B, C]_t = c_t W_x;   r, B, C <- RMSNorm_dt, RMSNorm_B, RMSNorm_C
    delta_t = softplus(r_t W_dt + b_dt)        float32
    h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * c_t) (x) B_t      float32
    y_t = h_t C_t + D * c_t;   out_t = (y_t * silu(z_t)) W_out

with ``A = -exp(A_log)``. What a sequence carries from step to step is
``h`` [N, E] float32 (E on the lanes: ``kernels/pallas/selective_scan``)
and the last ``K - 1`` rows of ``u``: a constant size whatever its
length. Attention has grouped heads and **no positional encoding of any
kind** (the family has none); its keys and values are paged as any
model's.

Served through ``ServingEngine`` like the Llama file's model, on
``PagedServingModel``: the attention layers write the cache's pools, the
state-space layers the cache's recurrent state (``PagedKVCache``
``ssm_state``/``conv_state``), which rides the serving programs beside
the pools, donated and returned. The prefill runs the chunked scan
kernel and writes a slot's state from zero; the decode step updates every
live slot's state in place. ``docs/SERVING.md`` "Recurrent state beside
the paged cache" says what is refused with such a cache, and why.

Parameter layouts that differ from the Hugging Face port's, same
numbers: matrices are ``[in, out]``; ``conv_weight`` is ``[K, E]`` (the
port's ``conv1d.weight`` is ``[E, 1, K]``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.random import next_key
from ..core.tensor import Tensor
from ..inference.paged import RecurrentStateSpec
from ..kernels.pallas.selective_scan import (selective_scan_plain,
                                             selective_scan_routed,
                                             state_update_routed)
from ..nn import functional as F
from ..nn.initializer import Constant, Initializer
from ..profiler import metrics as _metrics
from ..profiler.tracing import phase as _phase
from ..profiler.tracing import scope as _scope
from .llama import LlamaMLP, PagedServingModel, _normal_attr

__all__ = ["Jamba", "JambaConfig"]

# true (unpadded) tokens through the prefill scan
_SCAN_TOKENS = _metrics.counter("serving.ssm.scan_tokens")
# the step size delta starts log-uniform in this range: the Mamba paper's
# initialisation, for which the family's config has no key
_DT_MIN, _DT_MAX = 1e-3, 1e-1


@dataclasses.dataclass
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28
    num_heads: int = 20
    num_kv_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if self.num_experts > 1:
            raise ValueError(
                "JambaConfig: num_experts > 1 (a sparse feed-forward in "
                "the expert layers) is not modelled; the dense members "
                "of the family publish num_experts 1.")
        if self.mamba_proj_bias:
            raise ValueError("JambaConfig: mamba_proj_bias is not "
                             "modelled; the family publishes false.")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def mamba_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def layers_block_type(self):
        return ["attention" if i % self.attn_layer_period
                == self.attn_layer_offset else "mamba"
                for i in range(self.num_layers)]

    @staticmethod
    def jamba2_3b():
        return JambaConfig()

    @staticmethod
    def tiny():
        """Two whole periods of 4 (attention at offset 2): 8 layers, E
        64, N 16, R 4, 4 query heads on one KV head."""
        return JambaConfig(vocab_size=256, hidden_size=32,
                           intermediate_size=64, num_layers=8, num_heads=4,
                           num_kv_heads=1, attn_layer_period=4,
                           attn_layer_offset=2, mamba_dt_rank=4,
                           max_position_embeddings=256)


class _ALogInit(Initializer):
    """``A_log[e, n] = log(n + 1)``: state n forgets at rate n + 1."""

    def __call__(self, shape, dtype):
        row = jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(row, shape).astype(dtype)


class _DtBiasInit(Initializer):
    """``softplus^-1(dt)``, ``dt`` log-uniform in [_DT_MIN, _DT_MAX]."""

    def __call__(self, shape, dtype):
        dt = jnp.exp(jax.random.uniform(
            next_key(), tuple(shape), jnp.float32, math.log(_DT_MIN),
            math.log(_DT_MAX)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class JambaMambaMixer(nn.Layer):
    def __init__(self, config: JambaConfig):
        super().__init__()
        d, e = config.hidden_size, config.mamba_inner
        n, r, k = (config.mamba_d_state, config.mamba_dt_rank,
                   config.mamba_d_conv)
        self.d_state, self.dt_rank, self.d_conv = n, r, k
        attr = _normal_attr(config.initializer_range)
        eps = config.rms_norm_eps
        self.in_proj = nn.Linear(d, 2 * e, weight_attr=attr,
                                 bias_attr=False)
        self.conv_weight = self.create_parameter([k, e], attr=attr)
        self.conv_bias = self.create_parameter([e], is_bias=True) \
            if config.mamba_conv_bias else None
        self.x_proj = nn.Linear(e, r + 2 * n, weight_attr=attr,
                                bias_attr=False)
        self.dt_layernorm = nn.RMSNorm(r, epsilon=eps)
        self.b_layernorm = nn.RMSNorm(n, epsilon=eps)
        self.c_layernorm = nn.RMSNorm(n, epsilon=eps)
        self.dt_proj = nn.Linear(
            r, e, weight_attr=attr, bias_attr=nn.ParamAttr(
                initializer=_DtBiasInit()))
        self.A_log = self.create_parameter([e, n],
                                           default_initializer=_ALogInit())
        self.D = self.create_parameter([e],
                                       default_initializer=Constant(1.0))
        self.out_proj = nn.Linear(e, d, weight_attr=attr, bias_attr=False)

    # everything below works on arrays, inside a traced program

    def _conv(self, window):
        """silu(b + sum_j w[j] * window[j]) of ``window`` [K, ..., E],
        summed and returned in float32."""
        w = self.conv_weight._data.astype(jnp.float32)
        acc = sum(w[j] * window[j].astype(jnp.float32)
                  for j in range(self.d_conv))
        if self.conv_bias is not None:
            acc = acc + self.conv_bias._data.astype(jnp.float32)
        return jax.nn.silu(acc)

    def _recurrence_inputs(self, c):
        """(delta before its softplus [.., E], B [.., N], C [.., N]) of
        the convolution's output ``c`` [.., E], all float32 from the
        matmuls' accumulators on: the recurrence sums what they feed it
        over thousands of steps, and a value that is rounded to the
        activations' type and widened again is one the compiler may or
        may not round (it keeps excess precision where it can)."""
        f32 = jnp.float32
        r, n = self.dt_rank, self.d_state
        rbc = Tensor(jnp.matmul(c, self.x_proj.weight._data,
                                preferred_element_type=f32))
        dt = self.dt_layernorm(rbc[..., :r])._data
        b = self.b_layernorm(rbc[..., r:r + n])._data
        cm = self.c_layernorm(rbc[..., r + n:])._data
        dt_pre = jnp.matmul(dt.astype(c.dtype), self.dt_proj.weight._data,
                            preferred_element_type=f32) \
            + self.dt_proj.bias._data.astype(f32)
        return dt_pre, b, cm

    def _a_t(self):
        """``A`` as the kernels take it: [N, E] float32."""
        return -jnp.exp(self.A_log._data.astype(jnp.float32).T)

    def _out(self, y, z):
        return self.out_proj(Tensor(y.astype(z.dtype) * jax.nn.silu(z)))._data

    def sequence(self, x, h0, tail0, n_true, scan):
        """The mixer over one sequence ``x`` [S, hidden] (normed) that
        continues from the state ``h0`` [N, E] and the convolution's
        tail ``tail0`` [K-1, E]; only its first ``n_true`` positions are
        real. ``scan`` is the recurrence (a route of
        ``selective_scan``). Returns (out [S, hidden], h after position
        ``n_true - 1``, the last K-1 rows of u before ``n_true``)."""
        s, k = x.shape[0], self.d_conv
        u, z = jnp.split(self.in_proj(Tensor(x))._data, 2, axis=-1)
        cat = jnp.concatenate([tail0.astype(u.dtype), u], axis=0)
        # the scan's kernel takes c in the activations' type: [S, E]
        # once through HBM at half the bytes
        c = self._conv(jnp.stack([cat[j:j + s] for j in range(k)])
                       ).astype(u.dtype)
        tail = jax.lax.dynamic_slice_in_dim(cat, n_true, k - 1, axis=0)
        dt_pre, b, cm = self._recurrence_inputs(c)
        y, h = scan(dt_pre, c, b, cm, self._a_t(), self.D._data, h0,
                    n_true)
        return self._out(y, z), h, tail

    def step(self, x, ssm, conv, layer, active, kernel_mode, sink=None):
        """One decode step of every slot: ``x`` [B, hidden] (normed);
        ``ssm`` [layers, B, N, E] and ``conv`` [layers, K-1, B, E] are
        the cache's stacked state, of which layer ``layer`` is stepped
        where ``active`` [B]. Returns (out [B, hidden], ssm, conv). A
        ``sink`` list is appended what moves ``h`` in this step (delta,
        c, B) of every slot."""
        u, z = jnp.split(self.in_proj(Tensor(x))._data, 2, axis=-1)
        tail = conv[layer]
        window = jnp.concatenate([tail, u[None].astype(tail.dtype)], axis=0)
        c = self._conv(window)            # float32 into the recurrence
        conv = conv.at[layer].set(
            jnp.where(active[None, :, None], window[1:], tail))
        dt_pre, b, cm = self._recurrence_inputs(c.astype(u.dtype))
        delta = jax.nn.softplus(dt_pre)
        if sink is not None:
            sink.append((delta, c, b))
        ssm, y = state_update_routed(
            ssm, layer, delta, c, b, cm, self._a_t(), self.D._data,
            active, kernel_mode=kernel_mode)
        return self._out(y, z), ssm, conv

    def forward(self, x):
        """The mixer over whole sequences from zero state: x
        [b, s, hidden] -> [b, s, hidden] (the plain scan)."""
        n, e = self.d_state, self.A_log.shape[0]

        def one(row):
            return self.sequence(
                row, jnp.zeros((n, e), jnp.float32),
                jnp.zeros((self.d_conv - 1, e), row.dtype),
                jnp.int32(row.shape[0]), selective_scan_plain)[0]

        return Tensor(jax.vmap(one)(x._data))


class JambaAttention(nn.Layer):
    """Grouped-query attention with no bias and no rotation."""

    def __init__(self, config: JambaConfig):
        super().__init__()
        d, hd = config.hidden_size, config.head_dim
        self.num_heads = config.num_heads
        self.num_kv_heads = config.num_kv_heads
        self.head_dim = hd
        attr = _normal_attr(config.initializer_range)
        self.q_proj = nn.Linear(d, self.num_heads * hd, weight_attr=attr,
                                bias_attr=False)
        self.k_proj = nn.Linear(d, self.num_kv_heads * hd,
                                weight_attr=attr, bias_attr=False)
        self.v_proj = nn.Linear(d, self.num_kv_heads * hd,
                                weight_attr=attr, bias_attr=False)
        self.o_proj = nn.Linear(self.num_heads * hd, d, weight_attr=attr,
                                bias_attr=False)

    def qkv(self, h, position_offset=0):
        """q [b, s, heads, hd], k, v [b, s, kv heads, hd] of the normed
        hidden ``h``. ``position_offset`` is the stack's and is not
        used: the family encodes no position."""
        b, s, _ = h.shape
        return (self.q_proj(h).reshape([b, s, self.num_heads,
                                        self.head_dim]),
                self.k_proj(h).reshape([b, s, self.num_kv_heads,
                                        self.head_dim]),
                self.v_proj(h).reshape([b, s, self.num_kv_heads,
                                        self.head_dim]))

    def forward(self, x):
        b, s, _ = x.shape
        q, k, v = self.qkv(x)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(out.reshape([b, s, -1]))


class JambaBlock(nn.Layer):
    """``self_attn`` or ``mixer``: the other is None, which is how the
    stack over the paged cache tells the two kinds of layer apart."""

    def __init__(self, config: JambaConfig, kind):
        super().__init__()
        eps = config.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=eps)
        self.self_attn = JambaAttention(config) if kind == "attention" \
            else None
        self.mixer = JambaMambaMixer(config) if kind == "mamba" else None
        self.pre_ff_layernorm = nn.RMSNorm(config.hidden_size, epsilon=eps)
        self.mlp = LlamaMLP(config)

    @property
    def post_attention_layernorm(self):
        """The norm in front of the feed-forward, under the name the
        shared stack (``PagedServingModel._paged_stack``) reads."""
        return self.pre_ff_layernorm

    def forward(self, x):
        with _scope("residual"):
            h = self.input_layernorm(x)
        if self.self_attn is None:
            with _scope("mixer"):
                out = self.mixer(h)
        else:
            with _scope("attn"):
                out = self.self_attn(h)
        with _scope("residual"):
            x = x + out
            h = self.pre_ff_layernorm(x)
        with _scope("ffn"):
            out = self.mlp(h)
        with _scope("residual"):
            return x + out


class Jamba(PagedServingModel):
    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        attr = _normal_attr(config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=attr)
        self.layers = nn.LayerList([JambaBlock(config, kind)
                                    for kind in config.layers_block_type])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.lm_head = None if config.tie_word_embeddings else nn.Linear(
            config.hidden_size, config.vocab_size, weight_attr=attr,
            bias_attr=False)
        kinds = config.layers_block_type
        self._kv_cache_layers = kinds.count("attention")
        self._recurrent_state = RecurrentStateSpec(
            layers=kinds.count("mamba"), channels=config.mamba_inner,
            states=config.mamba_d_state, conv_tail=config.mamba_d_conv - 1)

    # -- what the cache is built from (serving.Scheduler reads these) ----

    @property
    def kv_cache_layers(self):
        return self._kv_cache_layers

    @property
    def recurrent_state(self):
        return self._recurrent_state

    # -- the normal path: a full forward, no cache -----------------------

    def forward(self, input_ids):
        """Logits [b, s, vocab] of ``input_ids`` [b, s] from zero
        state."""
        x = self._embed(input_ids)
        for blk in self.layers:
            x = blk(x)
        with _scope("residual"):
            x = self.norm(x)
        return self._logits(x)

    # -- served path: programs over the paged cache and its state --------

    def _check_cache(self, cache):
        if cache.state_spec != self.recurrent_state \
                or cache.num_layers != self.kv_cache_layers:
            raise ValueError(
                "Jamba: the cache was not built for this model: it needs "
                f"{self.kv_cache_layers} layers of K and V pools and the "
                f"recurrent state {self.recurrent_state} "
                "(PagedKVCache(..., recurrent_state=model.recurrent_state)"
                ").")
        if cache.quantized:
            raise ValueError(
                "Jamba serves a bfloat16/float32 KV pool only: int8 KV "
                "(FLAGS_kv_cache_dtype=int8) has no program that carries "
                "recurrent state.")

    def _sequence_stack(self, x, t_start, t_total, from_zero, row, slot,
                        pools, state, mode, attend):
        """The stack over one sequence ``x`` [1, S, hidden] at the
        positions from ``t_start`` of slot ``slot`` (true positions end
        at ``t_total``): an attention layer writes its K and V row by row
        through the slot's table row ``row`` and attends by
        ``attend(q, k, v, k_pool, v_pool)``; a state-space layer
        continues from the slot's state (from zero where ``from_zero``)
        and leaves the state of the last true position there."""
        from ..inference.paged import paged_prefill_write_masked
        n_true = t_total - t_start
        fresh = {}

        def write(kp, vp, k, v):
            fresh["kv"] = (k, v)
            return paged_prefill_write_masked(
                kp, vp, row, k[0], v[0], t_start, t_start, t_total)

        def mix(mixer, h, state, j):
            # the slot's rows of layer j, read and written as slices in
            # place (an indexed update re-laid the whole array out)
            ssm, conv = state
            at = (jnp.int32(j), slot, jnp.int32(0), jnp.int32(0))
            at_tail = (jnp.int32(j), jnp.int32(0), slot, jnp.int32(0))
            n, e = ssm.shape[2:]
            h0 = jax.lax.dynamic_slice(ssm, at, (1, 1, n, e))[0, 0]
            tail0 = jax.lax.dynamic_slice(
                conv, at_tail, (1, conv.shape[1], 1, e))[0, :, 0]
            out, h1, tail = mixer.sequence(
                h._data[0], jnp.where(from_zero, 0.0, h0),
                jnp.where(from_zero, 0, tail0), n_true,
                lambda *a: selective_scan_routed(*a, kernel_mode=mode))
            return Tensor(out[None]), (
                jax.lax.dynamic_update_slice(ssm, h1[None, None], at),
                jax.lax.dynamic_update_slice(
                    conv, tail.astype(conv.dtype)[None, :, None], at_tail))

        return self._paged_stack(
            x, t_start, pools, write,
            lambda q, kp, vp: attend(q, *fresh["kv"], kp, vp),
            state=state, mix=mix)

    def _first_token(self, hidden, at, key, temp):
        return self._next_token(
            hidden, lambda logits: jnp.take_along_axis(
                logits, at[None, None, None], axis=1)[:, 0], key, temp)[0]

    def paged_prefill(self, cache, slot, prompt_ids, temperature=0.0,
                      pad_to=None, kernel_mode=None):
        """Run the prompt from zero state: the attention layers write its
        K and V into the slot's blocks, the state-space layers leave the
        slot's state at the last true position (whatever the slot held
        before is never read); sets ``seq_len`` and returns the first
        sampled token. ONE program a bucket ``pad_to``."""
        from ..inference.paged import resolve_paged_kernel
        self._check_cache(cache)
        mode = resolve_paged_kernel(kernel_mode)
        with self._paged_call(cache, "prefill", mode) as (call, rebind):
            with _phase("serving.prefill.forward"):
                s = int(np.asarray(prompt_ids).size)
                ids = self._padded(cache, prompt_ids, pad_to)
                tok, = call(
                    (jnp.asarray(ids), jnp.int32(s),
                     self._table_row(cache, slot),
                     jnp.int32(slot)),
                    (next_key(), jnp.float32(temperature)))
            with _phase("serving.prefill.pool_write",
                        layers=cache.num_layers, tokens=ids.shape[1]):
                rebind()
                cache.seq_lens[slot] = s
                cache.state_fresh[slot] = False
        _SCAN_TOKENS.inc(s)
        with _phase("serving.prefill.readback"):  # waits for the device
            return int(tok)

    def _build_prefill(self, quantized, mode):
        def body(ids_arr, true_len, row, slot, k_pools, v_pools, k_scales,
                 v_scales, state, key, temp):
            def attend(q, k, v, kp, vp):
                return F.scaled_dot_product_attention(
                    Tensor(q), Tensor(k), Tensor(v), is_causal=True)._data
            hidden, new, state = self._sequence_stack(
                self._embed(Tensor(ids_arr)), jnp.int32(0), true_len,
                True, row, slot, (k_pools, v_pools, k_scales, v_scales),
                state, mode, attend)
            return (self._first_token(hidden, true_len - 1, key, temp),
                    *new, state)
        return self._as_program(body, "jamba.paged_prefill", 5, mode=mode)

    def paged_prefill_extend(self, cache, slot, ids, tail_start,
                             write_start, temperature=0.0, pad_to=None,
                             kernel_mode=None):
        """Continue a slot: its table maps the K and V of
        ``[0, tail_start)`` and its state stands after position
        ``tail_start - 1`` (a slot that was just allocated has none, and
        ``tail_start`` is then 0); compute ``ids[tail_start:]``, write
        its K and V, attend it over the whole paged context and carry
        the state on. Sets ``seq_len`` and returns the first sampled
        token. ``write_start`` must be ``tail_start``: no position can
        be attended without advancing the state past it."""
        from ..inference.paged import resolve_paged_kernel
        self._check_cache(cache)
        fresh = bool(cache.state_fresh[slot])
        if write_start != tail_start or tail_start != (
                0 if fresh else int(cache.seq_lens[slot])):
            raise ValueError(
                f"Jamba.paged_prefill_extend: slot {slot}'s recurrent "
                f"state stands at position "
                f"{0 if fresh else int(cache.seq_lens[slot])}, so the "
                f"tail must start (and write) there, not at "
                f"{tail_start} (write {write_start}): a prefix's keys "
                "and values can be shared, the state at its end cannot.")
        mode = resolve_paged_kernel(kernel_mode)
        with _phase("serving.prefill.forward"):
            ids = np.asarray(ids).reshape(-1)
            total = ids.shape[0]
            tail = self._padded(cache, ids[tail_start:], pad_to)
            with self._paged_call(cache, "extend", mode) as (call, _):
                tok, = call(
                    (jnp.asarray(tail), jnp.int32(tail_start),
                     jnp.int32(total), jnp.bool_(fresh),
                     self._table_row(cache, slot),
                     jnp.int32(slot)),
                    (next_key(), jnp.float32(temperature)))
            cache.seq_lens[slot] = total
            cache.state_fresh[slot] = False
        _SCAN_TOKENS.inc(total - tail_start)
        with _phase("serving.prefill.readback"):  # waits for the device
            return int(tok)

    def _build_extend(self, quantized, mode):
        def body(tail_ids, t_start, t_total, from_zero, row, slot, k_pools,
                 v_pools, k_scales, v_scales, state, key, temp):
            from ..inference.paged import paged_prefix_attention_dense
            hidden, new, state = self._sequence_stack(
                self._embed(Tensor(tail_ids)), t_start, t_total,
                from_zero, row, slot,
                (k_pools, v_pools, k_scales, v_scales), state, mode,
                lambda q, k, v, kp, vp: paged_prefix_attention_dense(
                    q[0], kp, vp, row, t_start, t_total)[None])
            return (self._first_token(hidden, t_total - 1 - t_start, key,
                                      temp), *new, state)
        return self._as_program(body, "jamba.paged_extend", 7, mode=mode)

    def paged_decode_step(self, cache, last_tokens, active,
                          temperature=0.0, kernel_mode=None,
                          state_observer=None):
        """One decode step of every live slot, as the Llama file's: the
        attention layers write and attend the paged cache, the
        state-space layers step the slot's state in place (an inactive
        slot's stays as it is). Returns the tokens, still on the
        device.

        A debug tap (docs/OBSERVABILITY.md "The recurrence's tap"): the
        program also returns, for one slot, what moved each state-space
        layer's ``h`` in this very step, ONE float32 device array [state
        layers, 2 E + N + 1] (delta, c, B, and whether the slot was
        active), 1 MB at the published widths, that nobody reads back
        unless asked: ``state_observer()``, called under the cache's
        lock, gives None or ``(slot, list)``, and the list is appended
        the slot's array. The lengths move under the same lock, so
        whoever holds it sees state, lengths and list agree."""
        from ..inference.paged import resolve_paged_kernel
        self._check_cache(cache)
        mode = resolve_paged_kernel(kernel_mode)
        with self._paged_call(cache, "decode", mode) as (call, _):
            watched = state_observer() if state_observer else None
            toks, fed = call(
                (jnp.asarray(last_tokens, jnp.int32),),
                (cache.block_tables, jnp.asarray(cache.seq_lens),
                 jnp.asarray(active),
                 jnp.int32(watched[0] if watched else 0), next_key(),
                 jnp.float32(temperature)))
            act = np.asarray(active)
            cache.seq_lens = np.where(act, cache.seq_lens + 1,
                                      cache.seq_lens).astype(np.int32)
            if watched:
                watched[1].append(fed)
        return toks

    def _build_decode(self, quantized, mode):
        def body(toks, k_pools, v_pools, k_scales, v_scales, state, tables,
                 lens, active, probe, key, temp):
            from ..inference.paged import (paged_decode_attention,
                                           paged_decode_write)
            fed = []

            def mix(mixer, h, state, j):
                out, ssm, conv = mixer.step(h._data[:, 0], *state, j,
                                            active, mode, sink=fed)
                return Tensor(out[:, None]), (ssm, conv)

            hidden, new, state = self._paged_stack(
                self._embed(Tensor(toks[:, None])), lens,
                (k_pools, v_pools, k_scales, v_scales),
                lambda kp, vp, k, v: paged_decode_write(
                    kp, vp, tables, lens, k[:, 0], v[:, 0], active),
                lambda q, kp, vp: paged_decode_attention(
                    q[:, 0], kp, vp, tables,
                    jnp.where(active, lens + 1, lens), kernel_mode=mode),
                state=state, mix=mix)
            nxt = self._next_token(hidden, lambda logits: logits[:, 0],
                                   key, temp)
            on = active[probe].astype(jnp.float32)[None]
            probed = jnp.stack([jnp.concatenate(
                [part[probe].astype(jnp.float32) for part in layer] + [on])
                for layer in fed])
            return (nxt, probed, *new, state)
        return self._as_program(body, "jamba.paged_decode", 2, mode=mode)

    def apply_serving_mesh(self, mesh):
        if mesh is not None:
            raise ValueError(
                "Jamba is served on one device: a serving mesh "
                "(FLAGS_serving_mesh) has no sharding rule for the "
                "recurrent state that rides beside the KV pools, nor for "
                "the state-space mixers' parameters.")
