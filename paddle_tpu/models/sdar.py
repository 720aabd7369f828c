"""SDAR (``sdar_moe``): a sparse decoder that generates by diffusion over
blocks of tokens.

One layer, everything in the parameters' dtype except where said::

    n = RMSNorm(x);  q = W_q n, k = W_k n, v = W_v n      (no biases)
    q, k <- RMSNorm over each head's features, THEN rotary positions
    h = x + W_o attention(q, k, v)     key j visible to query i iff
                                       j // L <= i // L  (block-causal)
    m = RMSNorm(h)
    y = h + sum over the top-k experts e of  w_e  W_down,e (silu(W_gate,e m) * W_up,e m)
        w = softmax(W_r m) in float32 over all experts, the k largest,
        renormalised over those k (``norm_topk_prob``)

then a final RMSNorm and an untied head. A head's size is the
configuration's ``head_dim`` (``q_proj`` is hidden -> heads x head_dim),
not hidden / heads. The expert layer is ``distributed.moe.DroplessMoE``:
no capacity, no dropped token, no shared expert, no auxiliary loss.

Generation is by blocks of ``L = block_length`` positions
(``docs/SERVING.md`` "Block-diffusion decoding"): a block opens all
masked, each denoising forward runs the block's L ids against the cache
and each other and the most confident masked positions are unmasked
(``low_confidence_static``, in the same program: the open blocks are
the block step's input and its output, and stay on the device); once
none is masked one more forward (the commit) writes the block's keys
and values for good and its L tokens are emitted together. The model
declares ``tokens_per_block``; ``serving.Scheduler`` branches on that
and on nothing else.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..distributed.moe import DroplessMoE
from ..nn import functional as F
from ..profiler.tracing import phase as _phase
from ..profiler.tracing import scope as _scope
from .llama import PagedServingModel, _normal_attr, apply_rope

__all__ = ["SDAR", "SDARConfig", "block_causal_mask",
           "low_confidence_static"]


@dataclasses.dataclass
class SDARConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    initializer_range: float = 0.02
    # generation (the released generate.py's names)
    block_length: int = 4
    mask_token_id: int = 151669
    # denoising forwards a fully masked block takes; None = block_length
    # (one position a forward). ``low_confidence_static`` is the one
    # schedule: M masked positions over S steps unmask M // S a step, one
    # more in the first M mod S steps
    denoise_steps: int = None
    # experts held here, (lo, hi); None = all (expert parallelism gives
    # each holder a range)
    expert_range: tuple = None

    def __post_init__(self):
        if self.denoise_steps is None:
            self.denoise_steps = self.block_length

    @staticmethod
    def sdar_30b_a3b():
        return SDARConfig()

    @staticmethod
    def tiny():
        """2 layers, 8 experts, 2 a token, heads of 32 on a hidden of
        64 (so head_dim is not hidden / heads), blocks of 4."""
        return SDARConfig(vocab_size=256, hidden_size=64, num_layers=2,
                          num_heads=4, num_kv_heads=2, head_dim=32,
                          num_experts=8, num_experts_per_tok=2,
                          moe_intermediate_size=48,
                          max_position_embeddings=256, mask_token_id=255,
                          denoise_steps=2)


def block_causal_mask(q_pos, k_pos, block_length):
    """[len(q_pos), len(k_pos)] bool: key j is visible to query i iff
    ``j // L <= i // L`` (blocks aligned to position 0)."""
    return (k_pos[None, :] // block_length) <= (q_pos[:, None]
                                                // block_length)


def low_confidence_static(ids, masked, opened, denoised, tokens, probs,
                          active, steps, mask_token_id):
    """The unmasking rule on every open block at once, in ``jax.numpy``
    (it runs inside ``jit_sdar_block_step``). Per slot: the block's
    ``ids`` [B, L] int32, which positions are ``masked`` [B, L] bool, the
    masked positions it ``opened`` with and the denoising forwards it has
    had (``denoised``) [B]; of the forward just run, each position's
    arg-max ``tokens`` and their probabilities ``probs`` [B, L].

    An active slot with masked positions is denoised: forward ``t`` of
    ``steps`` unmasks the ``opened // steps`` (one more while ``t <
    opened % steps``) masked positions whose arg-max token is most
    probable, ties to the earlier position, and puts that token there. A
    position's rank is counted by comparing it with every other: L is
    small, and no sort is needed. An active slot with none masked has
    had its commit forward: its next block opens all masked. A slot not
    active keeps its state. Returns the state after, (ids, masked,
    opened, denoised), and the positions unmasked [B, L] bool."""
    width = ids.shape[1]
    steps = int(steps)
    any_masked = jnp.any(masked, axis=1)
    denoise = active & any_masked
    commit = active & ~any_masked
    n = jnp.where(denoise, opened // steps
                  + (denoised < opened % steps).astype(opened.dtype), 0)
    conf = jnp.where(masked, probs, -jnp.inf)
    mine, other = conf[:, :, None], conf[:, None, :]
    pos = jnp.arange(width)
    ahead = (other > mine) | ((other == mine)
                              & (pos[None, None, :] < pos[None, :, None]))
    rank = jnp.sum(ahead, axis=-1)
    picked = (rank < n[:, None]) & masked
    fresh = commit[:, None]
    ids_next = jnp.where(fresh, jnp.asarray(mask_token_id, ids.dtype),
                         jnp.where(picked, tokens.astype(ids.dtype), ids))
    masked_next = fresh | (masked & ~picked)
    opened_next = jnp.where(commit, width, opened).astype(opened.dtype)
    denoised_next = jnp.where(
        commit, 0, denoised + denoise.astype(denoised.dtype))
    return (ids_next, masked_next, opened_next, denoised_next), picked


def _block_state(xp, ids, masked, opened, denoised):
    """``SDAR.block_state``'s layout, in numpy or ``jax.numpy``."""
    return xp.concatenate(
        [ids, masked, opened[:, None], denoised[:, None]],
        axis=1).astype(xp.int32)


class SDARAttention(nn.Layer):
    def __init__(self, config: SDARConfig):
        super().__init__()
        d, hd = config.hidden_size, config.head_dim
        self.num_heads = config.num_heads
        self.num_kv_heads = config.num_kv_heads
        self.head_dim = hd
        self.rope_theta = config.rope_theta
        attr = _normal_attr(config.initializer_range)
        self.q_proj = nn.Linear(d, self.num_heads * hd, weight_attr=attr,
                                bias_attr=False)
        self.k_proj = nn.Linear(d, self.num_kv_heads * hd,
                                weight_attr=attr, bias_attr=False)
        self.v_proj = nn.Linear(d, self.num_kv_heads * hd,
                                weight_attr=attr, bias_attr=False)
        self.o_proj = nn.Linear(self.num_heads * hd, d, weight_attr=attr,
                                bias_attr=False)
        self.q_norm = nn.RMSNorm(hd, epsilon=config.rms_norm_eps)
        self.k_norm = nn.RMSNorm(hd, epsilon=config.rms_norm_eps)

    def qkv(self, h, position_offset=0):
        """q [b, s, heads, hd], k, v [b, s, kv heads, hd] of the normed
        hidden ``h``: per-head RMSNorm on q and k, then rotary positions
        from ``position_offset`` (a scalar, or one offset a row of the
        batch)."""
        b, s, _ = h.shape
        q = self.q_proj(h).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(h).reshape([b, s, self.num_kv_heads,
                                    self.head_dim])
        v = self.v_proj(h).reshape([b, s, self.num_kv_heads,
                                    self.head_dim])
        q, k = apply_rope(self.q_norm(q), self.k_norm(k),
                          theta=self.rope_theta,
                          position_offset=position_offset)
        return q, k, v

    def out(self, attn):
        b, s = attn.shape[:2]
        return self.o_proj(attn.reshape([b, s, -1]))


class SDARBlock(nn.Layer):
    def __init__(self, config: SDARConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_norm_eps)
        self.self_attn = SDARAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = DroplessMoE(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts, config.num_experts_per_tok,
            norm_topk_prob=config.norm_topk_prob,
            expert_range=config.expert_range,
            weight_attr=_normal_attr(config.initializer_range))


class SDAR(PagedServingModel):
    def __init__(self, config: SDARConfig):
        super().__init__()
        self.config = config
        attr = _normal_attr(config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=attr)
        self.layers = nn.LayerList([SDARBlock(config)
                                    for _ in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 weight_attr=attr, bias_attr=False)

    @property
    def tokens_per_block(self):
        """Positions a decode step of this model holds a slot: the
        scheduler's one sign that a step is a block's forward and not
        one token's."""
        return self.config.block_length

    # -- the normal path: a full forward under the block-causal mask -----

    def _layer(self, blk, x, mask, kernel_mode=None, kv_sink=None,
               counts_sink=None, attention=True):
        """One layer on x [1, s, d] under ``mask`` [s, s]. With
        ``attention`` False the layer stops at its keys and values (the
        last layer of a prefill, whose output nobody reads)."""
        attn = blk.self_attn
        with _scope("residual"):
            u = blk.input_layernorm(x)
        with _scope("attn"), jax.named_scope(attn._name_scope):
            q, k, v = attn.qkv(u)
            if kv_sink is not None:
                kv_sink.append((k, v))
            if not attention:
                return x
            out = attn.out(F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask))
        with _scope("residual"):
            h = x + out
            m = blk.post_attention_layernorm(h)
        with _scope("ffn"):
            out = blk.mlp(m, kernel_mode=kernel_mode,
                          counts_sink=counts_sink)
        with _scope("residual"):
            return h + out

    def forward(self, input_ids, kernel_mode=None, counts_sink=None):
        """Logits [b, s, vocab] of ``input_ids`` [b, s] under the
        block-causal mask, positions from 0."""
        s = input_ids.shape[1]
        pos = jnp.arange(s, dtype=jnp.int32)
        mask = block_causal_mask(pos, pos, self.config.block_length)
        x = self._embed(input_ids)
        for n, blk in enumerate(self.layers):
            with jax.named_scope(f"layers.{n}"):
                x = self._layer(blk, x, mask, kernel_mode=kernel_mode,
                                counts_sink=counts_sink)
        with _scope("residual"):
            x = self.norm(x)
        return self._logits(x)

    # -- served path: programs over the paged cache ----------------------

    def _check_cache(self, cache):
        if cache.quantized:
            raise ValueError(
                "SDAR serves a bfloat16/float32 KV pool only: int8 KV "
                "(FLAGS_kv_cache_dtype=int8) has no block-step program.")
        if cache.block_size % self.config.block_length:
            raise ValueError(
                f"SDAR: the KV block_size {cache.block_size} must be a "
                f"multiple of block_length {self.config.block_length}, "
                "so that a page's keys depend on nothing after it.")

    def paged_prefill(self, cache, slot, prompt_ids, temperature=0.0,
                      pad_to=None, kernel_mode=None):
        """Run ``prompt_ids`` (whole blocks: a multiple of
        ``block_length``) through the block-causal forward and write
        every layer's keys and values into the slot's blocks; sets
        ``seq_len``. Returns None: a block-diffusion prefill samples no
        token (the first block opens masked)."""
        from ..inference.paged import resolve_paged_kernel
        self._check_cache(cache)
        mode = resolve_paged_kernel(kernel_mode)
        with self._paged_call(cache, "prefill", mode) as (call, rebind):
            with _phase("serving.prefill.forward"):
                n = int(np.asarray(prompt_ids).size)
                ids = self._padded(cache, prompt_ids, pad_to)
                call((jnp.asarray(ids), jnp.int32(n),
                      self._table_row(cache, slot)))
            with _phase("serving.prefill.pool_write",
                        layers=cache.num_layers, tokens=ids.shape[1]):
                rebind()
                cache.seq_lens[slot] = n

    def _build_prefill(self, quantized, mode):
        block_length = self.config.block_length

        def body(ids_arr, n, row, k_pools, v_pools, k_scales, v_scales):
            from ..inference.paged import paged_prefill_write_masked
            s = ids_arr.shape[1]
            pos = jnp.arange(s, dtype=jnp.int32)
            mask = block_causal_mask(pos, pos, block_length)
            sink = []
            x = self._embed(Tensor(ids_arr))
            last = len(self.layers) - 1
            for i, blk in enumerate(self.layers):
                with jax.named_scope(f"layers.{i}"):
                    x = self._layer(blk, x, mask, kernel_mode=mode,
                                    kv_sink=sink, attention=i < last)
            new_k, new_v = [], []
            # row by row, the padding to the null block: a scatter of
            # whole [16, 4, 128] pages makes the v5e compiler re-lay the
            # pool out and copy it twice a layer (PERF.md, PR 28)
            zero = jnp.int32(0)
            for i, (k, v) in enumerate(sink):
                # the cache write is the attention sublayer's
                with jax.named_scope(f"layers.{i}"), _scope("attn"):
                    kp, vp = paged_prefill_write_masked(
                        k_pools[i], v_pools[i], row, k._data[0],
                        v._data[0], zero, zero, n)
                new_k.append(kp)
                new_v.append(vp)
            return new_k, new_v, k_scales, v_scales
        return self._as_program(body, "sdar.paged_prefill", 4, mode=mode)

    def paged_prefill_extend(self, cache, slot, ids, tail_start,
                             write_start, temperature=0.0, pad_to=None,
                             kernel_mode=None):
        """Prefix hit or re-prefill: the slot's table already maps the
        keys and values of ``[0, tail_start)``; compute only the tail
        (whole blocks, so ``tail_start`` is block-aligned), write it and
        attend it block-causally over the whole paged context."""
        from ..inference.paged import resolve_paged_kernel
        self._check_cache(cache)
        mode = resolve_paged_kernel(kernel_mode)
        with _phase("serving.prefill.forward"):
            ids = np.asarray(ids).reshape(-1)
            total = ids.shape[0]
            tail = self._padded(cache, ids[tail_start:], pad_to)
            with self._paged_call(cache, "extend", mode) as (call, _):
                call((jnp.asarray(tail), jnp.int32(tail_start),
                      jnp.int32(write_start), jnp.int32(total),
                      self._table_row(cache, slot)))
            cache.seq_lens[slot] = total

    def _extend_layer(self, blk, x, i, last, new_k, new_v, k_pool, v_pool,
                      row, t_start, w_start, t_total, mode):
        """Layer ``i`` of the tail-extend program on the tail ``x``: its
        keys and values written row by row, then (unless ``last``) the
        tail attended over the slot's paged context and the expert
        layer."""
        from ..inference.paged import (paged_prefill_write_masked,
                                       paged_prefix_attention_dense)
        attn = blk.self_attn
        with jax.named_scope(f"layers.{i}"):
            with _scope("residual"):
                u = blk.input_layernorm(x)
            with _scope("attn"), jax.named_scope(attn._name_scope):
                q, k, v = attn.qkv(u, position_offset=t_start)
                kp, vp = paged_prefill_write_masked(
                    k_pool, v_pool, row, k._data[0], v._data[0], t_start,
                    w_start, t_total)
                new_k.append(kp)
                new_v.append(vp)
                if last:
                    return x
                out = attn.out(Tensor(paged_prefix_attention_dense(
                    q._data[0], kp, vp, row, t_start, t_total,
                    block_len=self.config.block_length)[None]))
            with _scope("residual"):
                h = x + out
                m = blk.post_attention_layernorm(h)
            with _scope("ffn"):
                out = blk.mlp(m, kernel_mode=mode)
            with _scope("residual"):
                return h + out

    def _build_extend(self, quantized, mode):
        def body(tail_ids, t_start, w_start, t_total, row, k_pools,
                 v_pools, k_scales, v_scales):
            new_k, new_v = [], []
            x = self._embed(Tensor(tail_ids))
            last = len(self.layers) - 1
            # not ``_paged_stack``: the last layer stops at its keys and
            # values, as the prefill's does
            for i, blk in enumerate(self.layers):
                x = self._extend_layer(
                    blk, x, i, i == last, new_k, new_v, k_pools[i],
                    v_pools[i], row, t_start, w_start, t_total, mode)
            return new_k, new_v, k_scales, v_scales
        return self._as_program(body, "sdar.paged_extend", 6, mode=mode)

    def block_state(self, ids, masked, opened, denoised):
        """The open blocks as the block step takes and returns them: ONE
        int32 array [B, 2 L + 2] of, per slot, the block's ids [L], which
        of its positions are masked [L] (its own columns: an arg-max may
        be ``mask_token_id``), the masked positions it opened with and
        the denoising forwards it has had."""
        return _block_state(np, *map(np.asarray,
                                     (ids, masked, opened, denoised)))

    def paged_block_step(self, cache, state, active, kernel_mode=None,
                         moe_sink=None):
        """One forward of every active slot's open block and the
        unmasking rule on what it gave. ``state`` (``block_state``; a
        host array, or the array the step before returned, still on the
        device) holds the open blocks: a slot's ids sit at positions
        ``[seq_len, seq_len + L)``, their keys and values are written
        there (overwriting the last forward's) and each row attends
        ``seq_len + L`` keys, the block's own among them, with no mask
        inside the block. A slot with masked positions is denoised
        (``low_confidence_static``); a slot with none has had its commit
        forward, and its next block opens all masked. ``seq_lens`` do not
        move: the caller advances a slot whose block commits.

        Returns two device arrays. The first is float32 and ONE read
        brings everything back: ``unpack_block_step`` splits it into, per
        position, the arg-max token, its logit and its softmax
        probability, the id and the mask the forward was fed, whether the
        rule unmasked it, and the rows routed to each expert of each
        layer. The second is the state after the step, the next step's
        input. A ``moe_sink`` list is appended what each layer's expert
        layer saw and gave in this very program over the ``B * L`` rows,
        two device arrays (each output of the program costs the host
        50 us a step, chip runs PR 28): (input, output) [2, layers,
        rows, hidden] and the router's (weights, expert ids) [2,
        layers, rows, k] float32. Nothing reads them back unless the
        caller does."""
        from ..inference.paged import resolve_paged_kernel
        self._check_cache(cache)
        mode = resolve_paged_kernel(kernel_mode)
        with self._paged_call(cache, "block_step", mode) as (call, _):
            packed, state, moe = call(
                (jnp.asarray(state, jnp.int32),),
                (cache.block_tables, jnp.asarray(cache.seq_lens),
                 jnp.asarray(active)))
        # the read-back's transfer queues behind the program now, not
        # when the host comes to ask for it
        packed.copy_to_host_async()
        if moe_sink is not None:
            moe_sink.append(moe)
        return packed, state

    def unpack_block_step(self, packed, batch):
        """``paged_block_step``'s first array, read to the host: a dict
        of ``tokens`` [B, L] int64, ``logits`` and ``probs`` [B, L], the
        ``ids`` [B, L] int64 and ``masked`` [B, L] bool the forward was
        fed, ``unmasked`` [B, L] bool (the positions the rule unmasked)
        and ``expert_rows`` [layers, experts] int64."""
        cfg = self.config
        n = batch * cfg.block_length
        packed = np.asarray(packed)
        per_position = packed[:6 * n].reshape(6, batch, cfg.block_length)
        return {"tokens": per_position[0].astype(np.int64),
                "logits": per_position[1], "probs": per_position[2],
                "ids": per_position[3].astype(np.int64),
                "masked": per_position[4] > 0,
                "unmasked": per_position[5] > 0,
                "expert_rows": packed[6 * n:].astype(np.int64).reshape(
                    cfg.num_layers, cfg.num_experts)}

    def _build_block_step(self, quantized, mode):
        cfg = self.config
        block_length = cfg.block_length

        def body(state, k_pools, v_pools, k_scales, v_scales, tables, lens,
                 active):
            from ..inference.paged import (paged_block_attention,
                                           paged_spec_write)
            ids = state[:, :block_length]
            masked = state[:, block_length:2 * block_length] > 0
            opened, denoised = state[:, -2], state[:, -1]
            b = ids.shape[0]
            whole = jnp.full((b,), block_length, jnp.int32)
            seen = jnp.where(active, lens + block_length, lens)
            counts, routed, moe_in, moe_out = [], [], [], []

            def experts(blk, m):
                y = blk.mlp(m, kernel_mode=mode, counts_sink=counts,
                            route_sink=routed,
                            valid=Tensor(jnp.repeat(active, block_length)),
                            kernel_tag="_step")
                moe_in.append(m._data.reshape(-1, m.shape[-1]))
                moe_out.append(y._data.reshape(-1, y.shape[-1]))
                return y

            x, new, _ = self._paged_stack(
                self._embed(Tensor(ids)), lens,
                (k_pools, v_pools, k_scales, v_scales),
                lambda kp, vp, k, v: paged_spec_write(
                    kp, vp, tables, lens, k, v, whole, active),
                lambda q, kp, vp: paged_block_attention(
                    q, kp, vp, tables, seen, kernel_mode=mode),
                mlp=experts)
            with _scope("head"):
                # float32 logits straight off the MXU's accumulator: a
                # bfloat16 round of them would move a probability by 2 %
                logits = jnp.matmul(x._data, self.lm_head.weight._data,
                                    preferred_element_type=jnp.float32)
                top = jnp.max(logits, axis=-1)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                prob = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]),
                                     axis=-1)
                # the rule ranks the very float32 probabilities the host
                # is handed
                after, picked = low_confidence_static(
                    ids, masked, opened, denoised, tok, prob, active,
                    cfg.denoise_steps, cfg.mask_token_id)
            f32 = jnp.float32
            packed = jnp.concatenate(
                [a.astype(f32).reshape(-1)
                 for a in (tok, top, prob, ids, masked, picked)]
                + [c._data.astype(f32) for c in counts])
            state = _block_state(jnp, *after)
            moe = (jnp.stack([jnp.stack(moe_in), jnp.stack(moe_out)]),
                   jnp.stack([jnp.stack([w._data for w, _ in routed]),
                              jnp.stack([e._data.astype(f32)
                                         for _, e in routed])]))
            return (packed, state, moe, *new)
        return self._as_program(body, "sdar.block_step", 2, mode=mode)

    def apply_serving_mesh(self, mesh):
        if mesh is not None:
            raise ValueError(
                "SDAR is served on one device: a serving mesh "
                "(FLAGS_serving_mesh) has no sharding rules for its "
                "stacked experts or its block-step program yet.")
