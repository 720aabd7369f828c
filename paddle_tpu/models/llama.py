"""Llama family (Llama-2/3 style decoder).

Capability parity target: the reference's semi-auto llama workload
(`test/auto_parallel/hybrid_strategy/semi_auto_llama.py`) and its fused
kernels (`fused_rope`, `fused_rms_norm`, flash attention — SURVEY.md §2.1).
TPU-first: RoPE and RMSNorm are plain jnp (XLA fuses them into neighbors),
attention is SDPA→Pallas flash with GQA, SwiGLU is two MXU matmuls + fused
elementwise. No KV-cache branching in the training path.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..nn import functional as F
from ..profiler.tracing import phase as _phase
from ..profiler.tracing import scope as _scope

# guards lazy creation of each model's paged-call lock (Llama._paged_lock)
_PAGED_LOCK_INIT = threading.Lock()


def _aot_wrap(jitted, tag):
    """Route a serving-path jit entry point through the persistent AOT
    compile cache (serving/aot_cache.py): a fresh process with a warm
    cache loads the serialized executable instead of compiling. The
    wrapper forwards straight to ``jitted`` until a cache dir is
    configured (FLAGS_serving_aot_cache / FLAGS_aot_cache_dir), so the
    production default is byte-for-byte plain jax.jit."""
    from ..serving.aot_cache import wrap
    return wrap(jitted, tag)


def _layer_scales(k_scales, v_scales, i):
    """Layer ``i``'s scale arrays as the keywords the cache's write and
    attention functions take them by: none for a full-precision cache,
    whose lists are empty."""
    return {"k_scale": k_scales[i], "v_scale": v_scales[i]} \
        if k_scales else {}


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = None  # GQA; None = MHA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    use_fp8: bool = False  # fp8 block linears (amp.fp8 delayed scaling)
    # loss() uses the blockwise fused LM-head CE (see models/gpt.py)
    fused_head_ce: bool = True
    # a head's size; None = hidden_size // num_heads. A configuration
    # may publish another (q_proj is then hidden -> num_heads * head_dim)
    head_dim: int = None

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads

    @staticmethod
    def llama2_7b():
        return LlamaConfig()

    @staticmethod
    def llama3_8b():
        return LlamaConfig(vocab_size=128256, hidden_size=4096,
                           intermediate_size=14336, num_layers=32,
                           num_heads=32, num_kv_heads=8,
                           max_position_embeddings=8192,
                           rope_theta=500000.0)

    @staticmethod
    def llama3_70b():
        return LlamaConfig(vocab_size=128256, hidden_size=8192,
                           intermediate_size=28672, num_layers=80,
                           num_heads=64, num_kv_heads=8,
                           max_position_embeddings=8192,
                           rope_theta=500000.0)

    @staticmethod
    def tiny():
        return LlamaConfig(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_layers=2, num_heads=4,
                           num_kv_heads=2, max_position_embeddings=64)

    @staticmethod
    def tiny_tp():
        """Mesh-friendly tiny config (docs/SERVING.md "Mesh-sharded
        serving"): 8 q and kv heads so the serving mesh's model axis
        can split 1..8 ways — ``tiny()``'s 4/2 heads cap it at 2.
        tools/mesh_gate.py, bench.py's ``mesh_serve`` rung, and
        tests/framework/test_mesh_serving.py all serve THIS config."""
        return LlamaConfig(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_layers=2,
                           num_heads=8, num_kv_heads=8,
                           max_position_embeddings=64)


def apply_rope(q, k, theta=10000.0, position_offset=0):
    """Rotary embedding on [b, s, h, d] Tensors (capability of the
    reference's fused_rotary_position_embedding, fused_ops.yaml:408)."""

    def _rope(qa, ka):
        d = qa.shape[-1]
        s = qa.shape[1]
        inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, jnp.float32) / d))
        off = jnp.asarray(position_offset, jnp.float32)
        if off.ndim == 1:  # per-batch offsets (paged decode slots)
            pos = off[:, None] + jnp.arange(s, dtype=jnp.float32)[None, :]
            freqs = pos[..., None] * inv_freq  # [b, s, d/2]
            cos = jnp.cos(freqs)[:, :, None, :]
            sin = jnp.sin(freqs)[:, :, None, :]
        else:
            pos = off + jnp.arange(s, dtype=jnp.float32)
            freqs = jnp.outer(pos, inv_freq)  # [s, d/2]
            cos = jnp.cos(freqs)[None, :, None, :]
            sin = jnp.sin(freqs)[None, :, None, :]

        def rot(x):
            x1 = x[..., 0::2].astype(jnp.float32)
            x2 = x[..., 1::2].astype(jnp.float32)
            o1 = x1 * cos - x2 * sin
            o2 = x2 * cos + x1 * sin
            out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
            return out.astype(x.dtype)

        return rot(qa), rot(ka)

    return apply(_rope, q, k, name="rope")


def _normal_attr(std):
    return nn.ParamAttr(initializer=nn.initializer.Normal(0.0, std))


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        d = config.hidden_size
        self.num_heads = config.num_heads
        self.num_kv_heads = config.num_kv_heads
        self.head_dim = config.head_dim
        self.rope_theta = config.rope_theta
        std = config.initializer_range
        q_out = self.num_heads * self.head_dim
        kv_out = self.num_kv_heads * self.head_dim
        self.q_proj = nn.Linear(d, q_out, weight_attr=_normal_attr(std),
                                bias_attr=False)
        self.k_proj = nn.Linear(d, kv_out, weight_attr=_normal_attr(std),
                                bias_attr=False)
        self.v_proj = nn.Linear(d, kv_out, weight_attr=_normal_attr(std),
                                bias_attr=False)
        self.o_proj = nn.Linear(q_out, d, weight_attr=_normal_attr(std),
                                bias_attr=False)

    def qkv(self, h, position_offset=0):
        """q [b, s, heads, hd], k, v [b, s, kv heads, hd] of the normed
        hidden ``h``, q and k with rotary positions from
        ``position_offset`` (a scalar, or one offset a row of the
        batch)."""
        b, s, _ = h.shape
        q = self.q_proj(h).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(h).reshape([b, s, self.num_kv_heads,
                                    self.head_dim])
        v = self.v_proj(h).reshape([b, s, self.num_kv_heads,
                                    self.head_dim])
        q, k = apply_rope(q, k, theta=self.rope_theta,
                          position_offset=position_offset)
        return q, k, v

    def forward(self, x, cache=None, position_offset=0, kv_sink=None):
        from .. import ops
        b, s, _ = x.shape
        d = self.num_heads * self.head_dim
        q, k, v = self.qkv(x, position_offset)
        if kv_sink is not None:  # paged prefill captures post-rope KV
            kv_sink.append((k, v))
        if cache is None:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            out = ops.reshape(out, [b, s, d])
            return self.o_proj(out)
        # decode/prefill with KV cache: cache = (k_cache, v_cache)
        # [b, max_s, kv_heads, head_dim] Tensors; write at position_offset,
        # attend against positions <= query position (static shapes for jit)
        k_cache, v_cache = cache

        def attend(qa, ka, va, kc, vc, off):
            z = jnp.int32(0)
            off32 = jnp.asarray(off, jnp.int32)
            kc = jax.lax.dynamic_update_slice(kc, ka.astype(kc.dtype),
                                              (z, off32, z, z))
            vc = jax.lax.dynamic_update_slice(vc, va.astype(vc.dtype),
                                              (z, off32, z, z))
            max_s = kc.shape[1]
            rep = qa.shape[2] // kc.shape[2]
            kf = jnp.repeat(kc, rep, axis=2) if rep > 1 else kc
            vf = jnp.repeat(vc, rep, axis=2) if rep > 1 else vc
            scale = 1.0 / (qa.shape[-1] ** 0.5)
            logits = jnp.einsum("bsnd,btnd->bnst", qa, kf,
                                preferred_element_type=jnp.float32) * scale
            pos_q = off + jnp.arange(qa.shape[1], dtype=jnp.int32)
            pos_k = jnp.arange(max_s, dtype=jnp.int32)
            mask = pos_k[None, :] <= pos_q[:, None]
            logits = jnp.where(mask[None, None], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1).astype(qa.dtype)
            out = jnp.einsum("bnst,btnd->bsnd", probs, vf)
            return out, kc, vc

        out, new_k, new_v = apply(attend, q, k, v, k_cache, v_cache,
                                  position_offset, name="cached_attention")
        out = ops.reshape(out, [b, s, d])
        return self.o_proj(out), (new_k, new_v)


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        d, i = config.hidden_size, config.intermediate_size
        std = config.initializer_range
        self.gate_proj = nn.Linear(d, i, weight_attr=_normal_attr(std),
                                   bias_attr=False)
        self.up_proj = nn.Linear(d, i, weight_attr=_normal_attr(std),
                                 bias_attr=False)
        self.down_proj = nn.Linear(i, d, weight_attr=_normal_attr(std),
                                   bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaBlock(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, cache=None, position_offset=0, kv_sink=None):
        with _scope("residual"):
            h = self.input_layernorm(x)
        new_cache = None
        with _scope("attn"):
            if cache is None:
                attn_out = self.self_attn(h, kv_sink=kv_sink)
            else:
                attn_out, new_cache = self.self_attn(
                    h, cache=cache, position_offset=position_offset)
        with _scope("residual"):
            x = x + attn_out
            h = self.post_attention_layernorm(x)
        with _scope("ffn"):
            out = self.mlp(h)
        with _scope("residual"):
            x = x + out
        return x if cache is None else (x, new_cache)


class PagedServingModel(nn.Layer):
    """What every model served through ``ServingEngine`` shares: its
    serving programs in one dict, the one way they are built, named and
    called on a ``PagedKVCache`` (``_paged_call``), the decoder stack
    over the paged cache, and the AOT-cache tag that folds the serving
    mesh in.

    A serving program is ``program(param_arrays, *head, k_pools,
    v_pools, k_scales, v_scales[, state], *tail) -> (*outputs, k_pools,
    v_pools, k_scales, v_scales[, state])``: the cache's device state in
    the middle as the cache holds it (``PagedKVCache.pool_lists``: the
    scale lists empty for a full-precision cache, the second list too
    for a latent cache's one pool a layer; ``state``, the stacked recurrent state, only
    where the model has layers that carry one), donated together and
    returned written in place. ``_build_<job>(quantized, mode)`` builds
    the program of a job."""

    @property
    def kv_cache_layers(self):
        """Layers that write K and V pools: what the cache's
        ``num_layers`` is built from."""
        return self.config.num_layers

    @property
    def recurrent_state(self):
        """The ``inference.paged.RecurrentStateSpec`` of the layers that
        carry state from step to step instead of writing K and V; None
        where every layer is attention."""
        return None

    @property
    def paged_programs(self):
        """The serving programs built so far, keyed ``(job, quantized,
        kernel mode)``; the mode is None where a job's program does not
        depend on it. Cleared when the serving mesh changes, so that
        programs re-lower against the new shardings."""
        return self.__dict__.setdefault("_paged_programs", {})

    def serving_program(self, job, quantized=False, mode=None):
        """The program of ``job`` for an int8 (``quantized``) or a
        full-precision cache, built on first use."""
        key = (job, bool(quantized), mode)
        programs = self.paged_programs
        if key not in programs:
            programs[key] = getattr(self, "_build_" + job)(*key[1:])
        return programs[key]

    def _as_program(self, body, tag, pools_at, quantized=False,
                    mode=None):
        """``body(*head, k_pools, v_pools, k_scales, v_scales[, state],
        *tail)`` as the serving program ``jit_<tag, dots as underscores>[_q8]``,
        the name a profiler trace shows on the device's ``XLA Modules``
        line and the host's ``PjitFunction(<name>)`` events, so busy
        time splits by program (``benchmarks/span_reduce.py``). The
        parameters are its first argument (bound into the module for the
        trace); the arguments from ``pools_at`` that hold the cache (the
        four lists, and the recurrent state where the model declares
        one) are donated:
        it takes their buffers and writes in place, and the caller
        rebinds what comes back before anything reads the cache. AOT tag
        ``<tag>[.q8][.k-<mode>][.mesh<spec>]``."""
        rebind = self._param_rebind()

        def fn(param_arrays, *args):
            from ..core.autograd import no_grad
            rebind(param_arrays)
            with no_grad():
                return body(*args)

        if quantized:
            tag += ".q8"
        fn.__name__ = fn.__qualname__ = tag.replace(".", "_")
        if mode not in (None, "auto"):
            tag += f".k-{mode}"
        held = 4 + (self.recurrent_state is not None)
        return _aot_wrap(
            jax.jit(fn, donate_argnums=tuple(range(pools_at,
                                                   pools_at + held))),
            self._aot_tag(tag))

    def paged_call_args(self, cache, job, head, tail=(), mode=None):
        """``(program, args)``: the program of ``job`` for ``cache`` and
        the arguments a ``paged_*`` entry point calls it with — the
        parameters, ``head``, the cache's pools and scale arrays (and
        its recurrent state, where it holds one), ``tail``. For whoever lowers or runs the program beside the
        entry point; ``_paged_call`` is this plus the call."""
        return self.serving_program(job, cache.quantized, mode), (
            self._param_arrays(), *head, *cache.pool_lists(),
            *cache.state_args(), *tail)

    @contextlib.contextmanager
    def _paged_call(self, cache, job, mode=None):
        """THE call protocol of a serving program. Holds the model's lock
        and the cache's: nobody reads the cache between the dispatch,
        which deletes the pools it is handed, and the rebind of those it
        returns. Yields ``(call, rebind)``: ``call(head, tail)``
        dispatches the program and returns its other outputs;
        ``rebind()`` hands the cache its pools back, for an entry point
        that times that (the exit does it otherwise)."""
        with self._paged_lock(), cache.pool_lock:
            returned = []

            def call(head, tail=()):
                program, args = self.paged_call_args(cache, job, head,
                                                     tail, mode)
                held = 4 + len(cache.state_args())
                try:
                    out = program(*args)
                finally:
                    # tracing left tracers bound into the module's
                    # parameters; restore
                    self._param_rebind()(args[0])
                returned.append(out[-held:])
                return list(out[:-held])

            def rebind():
                cache.rebind_pools(*returned.pop())

            try:
                yield call, rebind
            finally:
                if returned:
                    rebind()

    @staticmethod
    def _padded(cache, ids, pad_to):
        """``ids`` as [1, S] int64 zero-padded to whole blocks, or to
        the bucket ``pad_to`` (serving/bucketing.py) under the slot's
        cap. Padding beyond a slot's allocated blocks is safe: those
        table entries are 0, the reserved null block, and everything
        past the true length is masked."""
        ids = np.asarray(ids).reshape(-1)
        bs = cache.block_size
        spad = -(-ids.shape[0] // bs) * bs
        if pad_to is not None:
            cap = cache.max_blocks_per_seq * bs
            spad = -(-min(max(int(pad_to), spad), cap) // bs) * bs
        out = np.zeros((1, spad), np.int64)
        out[0, :ids.shape[0]] = ids
        return out

    @staticmethod
    def _table_row(cache, slot):
        """The slot's table row as the prefill programs take it: a copy.
        A prefill that samples nothing is not waited for, and a backend
        may read a host array it was handed after the call returned (the
        CPU's does: a view of ``block_tables`` showed the program, 10
        times of 20, what the host wrote into it afterwards). The
        scheduler's next moves are on this row: it grows for the open
        block, and is zeroed if the slot is preempted."""
        return jnp.asarray(cache.block_tables[slot].copy())

    def _paged_stack(self, x, position_offset, pools, write, attend,
                     mlp=None, state=None, mix=None):
        """The decoder stack over the paged cache, written once, for
        both kinds of layer: ``x`` [b, s, d] is the embedded input of
        the positions from ``position_offset``. The ``i``-th attention
        layer hands its post-rope keys and values [b, s, Hk, D] to the
        job's ``write(k_pool, v_pool, k, v, **scales) -> (k_pool,
        v_pool, *scales)`` and its queries to ``attend(q, k_pool,
        v_pool, **scales)`` over what was written (any shape of b x s
        rows). Where the cache holds one pool a layer (its second list
        is empty: a latent cache), ``attn.qkv`` gives the queries and
        the one row, and the pair is ``write(pool, row) -> (pool,)`` and
        ``attend(q, pool)``. A layer whose ``self_attn`` is None carries state
        instead: the ``j``-th such hands its ``mixer`` and its normed
        input to the job's ``mix(mixer, h, state, j) -> (out, state)``.
        ``pools`` are the cache's four lists, ``state`` its recurrent
        state; ``mlp(blk, m)`` stands in for ``blk.mlp(m)``. How a
        sublayer reads and writes the residual is the model's
        (``residual_read``, ``residual_write``, ``residual_close``: ``x``
        may be more than one stream). Returns the final norm's output,
        the four lists written and the state."""
        k_pools, v_pools, k_scales, v_scales = pools
        b, s = x.shape[:2]
        new = ([], [], [], [])
        carried = 0
        for n, blk in enumerate(self.layers):
            # what a trace shows of an operation in here:
            # ``layers.<n>/pt.<component>/<sublayer>/../<operation>``
            with jax.named_scope(f"layers.{n}"):
                attn = blk.self_attn
                with _scope("residual"):
                    u, mixed = self.residual_read(blk, 0, x)
                    h = blk.input_layernorm(u)
                if attn is None:
                    mixer = blk.mixer
                    with _scope("mixer"), jax.named_scope(mixer._name_scope):
                        out, state = mix(mixer, h, state, carried)
                    carried += 1
                else:
                    with _scope("attn"), jax.named_scope(attn._name_scope):
                        i = len(new[0])
                        q, *rows = attn.qkv(h, position_offset)
                        held = [pools_of[i]
                                for pools_of in (k_pools, v_pools)
                                if pools_of]
                        scales = _layer_scales(k_scales, v_scales, i)
                        layer = write(*held, *(r._data for r in rows),
                                      **scales)
                        for pool_list, pool in zip(new, layer):
                            pool_list.append(pool)
                        out = attend(q._data, *layer[:len(held)],
                                     **dict(zip(scales,
                                                layer[len(held):])))
                        out = attn.o_proj(Tensor(out.reshape(b, s, -1)))
                with _scope("residual"):
                    x = self.residual_write(blk, 0, x, out, mixed)
                    u, mixed = self.residual_read(blk, 1, x)
                    m = blk.post_attention_layernorm(u)
                with _scope("ffn"):
                    out = blk.mlp(m) if mlp is None else mlp(blk, m)
                with _scope("residual"):
                    x = self.residual_write(blk, 1, x, out, mixed)
        with _scope("residual"):
            return self.norm(self.residual_close(x)), new, state

    # -- the residual path: one stream, each sublayer's output added ------
    # (a model with another path overrides the three: models/xing.py)

    def residual_read(self, blk, sublayer, x):
        """What sublayer ``sublayer`` (0: attention or mixer, 1: the
        feed-forward part) of ``blk`` reads of the residual ``x``, and
        whatever ``residual_write`` needs of ``x`` as it stood."""
        return x, None

    def residual_write(self, blk, sublayer, x, out, mixed):
        """The residual after the sublayer gave ``out``."""
        return x + out

    def residual_close(self, x):
        """The residual as the final norm reads it."""
        return x

    def _embed(self, ids):
        """The embedding of ``ids``, which starts the residual path."""
        with _scope("residual"):
            return self.embed_tokens(ids)

    def _logits(self, hidden):
        with _scope("head"):
            if self.lm_head is not None:
                return self.lm_head(hidden)
            from .. import ops
            return ops.matmul(hidden, self.embed_tokens.weight,
                              transpose_y=True)

    def _next_token(self, hidden, pick, key=None, temp=None):
        """The head over the stack's normed output, at the positions
        ``pick`` takes from the logits [b, s, vocab]: their arg-max, or
        (``key`` given) a sample at temperature ``temp`` where that is
        positive."""
        from .generation import sample_token
        logits = self._logits(hidden)._data
        with _scope("head"):
            last = pick(logits)

            def greedy():
                return jnp.argmax(last, axis=-1).astype(jnp.int32)

            if key is None:
                return greedy()
            return jax.lax.cond(
                temp > 0,
                lambda: sample_token(last / jnp.maximum(temp, 1e-6),
                                     temperature=1.0, key=key),
                greedy)

    def _param_rebind(self):
        if not hasattr(self, "_pb_names"):
            self._pb_names = [n for n, _ in self.named_parameters()]
        if hasattr(self, "_pb_rebind"):
            return self._pb_rebind

        def rebind(param_arrays):
            for n, arr in zip(self._pb_names, param_arrays):
                obj = self
                *path, leaf = n.split(".")
                for seg in path:
                    obj = obj[int(seg)] if seg.isdigit() else \
                        getattr(obj, seg)
                getattr(obj, leaf)._data = arr
        self._pb_rebind = rebind
        return rebind

    def _param_arrays(self):
        return tuple(p._data for _, p in self.named_parameters())

    def serving_mesh(self):
        """The ServingMesh this model's serving params are laid out
        on, or None (single-device serving)."""
        return self.__dict__.get("_serving_mesh")

    def _aot_tag(self, base):
        """AOT-cache tag for a serving program: the mesh spec folds in
        so fingerprints differ across mesh shapes even where the
        lowered text happens to agree (tests/framework/
        test_mesh_serving.py pins the distinction)."""
        mesh = self.__dict__.get("_serving_mesh")
        return base if mesh is None else f"{base}.mesh{mesh.spec}"

    def _paged_lock(self):
        """Per-model lock serializing the paged jit entry points. Their
        trace path REBINDS the module's parameters to tracers and
        restores them after the call — with several serving engines
        sharing one model (in-process fleet replicas), an unsynchronized
        cold-start races another thread's restore and leaks tracers into
        the shared params. One uncontended acquire per warm call is
        noise next to the dispatch itself. Created lazily in __dict__
        (not through Layer attr tracking; models stay picklable until
        first serve)."""
        lock = self.__dict__.get("_paged_call_lock")
        if lock is None:
            with _PAGED_LOCK_INIT:
                lock = self.__dict__.get("_paged_call_lock")
                if lock is None:
                    lock = threading.Lock()
                    self.__dict__["_paged_call_lock"] = lock
        return lock

    def num_params(self):
        return sum(p.size for p in self.parameters())


class Llama(PagedServingModel):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        std = config.initializer_range
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=_normal_attr(std))
        self.layers = nn.LayerList([LlamaBlock(config)
                                    for _ in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     weight_attr=_normal_attr(std),
                                     bias_attr=False)
        else:
            self.lm_head = None
        if config.use_fp8:
            from ..amp.fp8 import convert_to_fp8
            convert_to_fp8(self, exclude=("lm_head",))

    def forward(self, input_ids, caches=None, position_offset=0,
                kv_sink=None):
        new_caches = None
        if caches is None:
            x = self.forward_hidden(input_ids, kv_sink=kv_sink)
        else:
            x = self._embed(input_ids)
            new_caches = []
            for i, block in enumerate(self.layers):
                x, c = block(x, cache=caches[i],
                             position_offset=position_offset)
                new_caches.append(c)
            with _scope("residual"):
                x = self.norm(x)
        logits = self._logits(x)
        if caches is None:
            return logits
        return logits, new_caches

    def init_cache(self, batch_size, max_seq_len, dtype=None):
        """Allocate empty KV caches: per layer (k, v) of
        [b, max_s, kv_heads, head_dim]."""
        from .. import ops
        dt = dtype or (self.embed_tokens.weight.dtype)
        kvh = self.config.num_kv_heads
        hd = self.config.head_dim
        return [(ops.zeros([batch_size, max_seq_len, kvh, hd], dt),
                 ops.zeros([batch_size, max_seq_len, kvh, hd], dt))
                for _ in range(self.config.num_layers)]

    def generate(self, input_ids, max_new_tokens=32, **kwargs):
        from .generation import generate
        return generate(self, input_ids, max_new_tokens=max_new_tokens,
                        **kwargs)

    # -- paged (block) KV-cache decode ------------------------------------
    # Reference: block_multi_head_attention_kernel.cu (paged cache) +
    # masked_multihead_attention_kernel.cu (decode). See inference/paged.py.

    def apply_serving_mesh(self, mesh):
        """Lay the model out for mesh-sharded serving
        (serving/mesh.py; docs/SERVING.md "Mesh-sharded serving"):
        every parameter is ``device_put`` with its ``NamedSharding``
        along the mesh's model axis (column-parallel q/k/v/gate/up,
        row-parallel o/down, everything else replicated) and the
        serving programs built so far drop so they re-lower sharded —
        their AOT tags fold the mesh shape in (``_aot_tag``), so a
        1x8 executable can never be served from a 1x1 cache entry.
        Idempotent for the same mesh spec; ``mesh=None`` is a no-op
        (a previously-meshed model keeps its layout — construct a
        fresh model for single-device serving)."""
        if mesh is None:
            return
        import jax

        mesh.validate_model(self.config)
        cur = self.__dict__.get("_serving_mesh")
        if cur is not None and cur.spec == mesh.spec:
            self.__dict__["_serving_mesh"] = mesh
            return
        with self._paged_lock():
            for n, p in self.named_parameters():
                p._data = jax.device_put(p._data, mesh.param_sharding(n))
            self.__dict__["_serving_mesh"] = mesh
            self.paged_programs.clear()

    def paged_prefill(self, cache, slot, prompt_ids, temperature=0.0,
                      pad_to=None):
        """Run the prompt through the dense forward (causal), write its
        post-rope KV into the slot's pool blocks, set seq_len, and return
        the first sampled token: ONE program, which takes the pools
        donated and writes them in place (``_build_prefill``).

        ``pad_to`` (serving/bucketing.py): pad the prompt to a bucketed
        length instead of the next block multiple, so warm serving traces
        a bounded set of prefill shapes (``_padded``)."""
        from ..core.random import next_key

        with self._paged_call(cache, "prefill") as (call, rebind):
            with _phase("serving.prefill.forward"):
                s = int(np.asarray(prompt_ids).size)
                ids = self._padded(cache, prompt_ids, pad_to)
                tok, = call(
                    (jnp.asarray(ids), jnp.int32(s),
                     jnp.asarray(cache.block_tables[slot])),
                    (next_key(), jnp.float32(temperature)))
            # what is left of the write on the host: the program wrote
            # into the pools it was handed; take them back
            with _phase("serving.prefill.pool_write",
                        layers=cache.num_layers, tokens=ids.shape[1]):
                rebind()
                cache.seq_lens[slot] = s
        with _phase("serving.prefill.readback"):  # waits for the device
            return int(tok)

    def _build_prefill(self, quantized, mode):
        """The dense causal prefill program: the prompt's logits at its
        last true position sampled, and every layer's post-rope K and V
        written, whole pages at a time, into the blocks of the slot's
        table row ``row``."""
        def body(ids_arr, true_len, row, k_pools, v_pools, k_scales,
                 v_scales, key, temp):
            from ..inference.paged import paged_prefill_write
            sink = []
            hidden = self.forward_hidden(Tensor(ids_arr), kv_sink=sink)
            tok = self._next_token(
                hidden, lambda logits: jnp.take_along_axis(
                    logits, (true_len - 1)[None, None, None],
                    axis=1)[:, 0], key, temp)
            new = ([], [], [], [])
            for i, (k, v) in enumerate(sink):
                # the cache write is the attention sublayer's
                with jax.named_scope(f"layers.{i}"), _scope("attn"):
                    layer = paged_prefill_write(
                        k_pools[i], v_pools[i], row, k._data[0],
                        v._data[0], **_layer_scales(k_scales, v_scales, i))
                for pool_list, pool in zip(new, layer):
                    pool_list.append(pool)
            return (tok[0], *new)
        return self._as_program(body, "llama.paged_prefill", 4, quantized)

    def paged_prefill_extend(self, cache, slot, ids, tail_start,
                             write_start, temperature=0.0, pad_to=None):
        """Prefix-cache prefill (inference/paged.py): the slot's block
        table already maps cached KV for positions ``[0, tail_start)``
        (mapped read-only at admission); compute ONLY the tail
        ``ids[tail_start:]`` — embed, rope at the absolute offset, write
        its KV into the pool (positions ``>= write_start`` only; a
        fully-covered prompt recomputes just its last token's query and
        writes nothing), and attend each tail token against the whole
        paged context. Sets seq_len and returns the first sampled token,
        exactly like ``paged_prefill`` — covered positions cost zero
        prefill FLOPs.

        ``pad_to`` buckets the TAIL length (serving/bucketing.py) so
        warm cache-hit traffic traces a bounded set of extend programs;
        padded rows write nothing (masked to the null block) and their
        outputs are never read.
        """
        from ..core.random import next_key

        with _phase("serving.prefill.forward"):
            ids = np.asarray(ids).reshape(-1)
            total = ids.shape[0]
            tail = self._padded(cache, ids[tail_start:], pad_to)
            with self._paged_call(cache, "extend") as (call, _):
                tok, = call(
                    (jnp.asarray(tail), jnp.int32(tail_start),
                     jnp.int32(write_start), jnp.int32(total),
                     jnp.asarray(cache.block_tables[slot])),
                    (next_key(), jnp.float32(temperature)))
            cache.seq_lens[slot] = total
        with _phase("serving.prefill.readback"):  # waits for the device
            return int(tok)

    def _build_extend(self, quantized, mode):
        """The tail-extend program of ``paged_prefill_extend``: each
        layer writes the tail row by row
        (``paged_prefill_write_masked``) and attends it over the slot's
        whole paged context."""
        def body(tail_ids, t_start, w_start, t_total, row, k_pools,
                 v_pools, k_scales, v_scales, key, temp):
            from ..inference.paged import (paged_prefill_write_masked,
                                           paged_prefix_attention_dense)
            hidden, new, _ = self._paged_stack(
                self._embed(Tensor(tail_ids)), t_start,
                (k_pools, v_pools, k_scales, v_scales),
                lambda kp, vp, k, v, **scales: paged_prefill_write_masked(
                    kp, vp, row, k[0], v[0], t_start, w_start, t_total,
                    **scales),
                lambda q, kp, vp, **scales: paged_prefix_attention_dense(
                    q[0], kp, vp, row, t_start, t_total, **scales))
            tok = self._next_token(
                hidden, lambda logits: jnp.take_along_axis(
                    logits, (t_total - 1 - t_start)[None, None, None],
                    axis=1)[:, 0], key, temp)
            return (tok[0], *new)
        return self._as_program(body, "llama.paged_extend", 6, quantized)

    def paged_decode_step(self, cache, last_tokens, active,
                          temperature=0.0, kernel_mode=None):
        """One decode step for every live slot: write the incoming token's
        KV at position seq_len, attend against the paged cache (masked to
        seq_len+1), sample the next token. Single static-shape jitted
        program; updates `cache` pools/lens in place.

        ``kernel_mode`` is the engine's construction-resolved
        ``FLAGS_paged_kernel`` (auto|pallas|dense) — it picks the
        attention route inside the traced program, so the decode program
        is kept PER MODE (engines with different routing can share one
        model without serving each other's programs)."""
        from ..core.random import next_key
        from ..inference.paged import resolve_paged_kernel

        mode = resolve_paged_kernel(kernel_mode)
        with self._paged_call(cache, "decode", mode) as (call, _):
            toks, = call(
                (jnp.asarray(last_tokens, jnp.int32),),
                (cache.block_tables, jnp.asarray(cache.seq_lens),
                 jnp.asarray(active), next_key(),
                 jnp.float32(temperature)))
        act = np.asarray(active)
        cache.seq_lens = np.where(act, cache.seq_lens + 1,
                                  cache.seq_lens).astype(np.int32)
        return toks

    def _build_decode(self, quantized, mode):
        """The batched decode program: each live slot's incoming token
        written into the pools (``paged_decode_write``), then attended
        against them by the route ``mode`` picks — an int8 pool
        dequantizes inside the Pallas kernel's VMEM gather on the pallas
        route, in the dense reference's XLA gather otherwise."""
        # mesh-sharded serving: captured at build time — the program is
        # rebuilt (apply_serving_mesh drops it) when the mesh changes.
        # A model-sharded mesh runs the attention explicitly sharded
        # per kv-head under shard_map.
        mesh = self.__dict__.get("_serving_mesh")
        use_tp = mesh is not None and mesh.shard_map_armed

        def body(toks, k_pools, v_pools, k_scales, v_scales, tables,
                 lens, active, key, temp):
            from ..inference.paged import (paged_decode_attention,
                                           paged_decode_attention_tp,
                                           paged_decode_write)

            attention = functools.partial(
                paged_decode_attention_tp, mesh=mesh) if use_tp \
                else paged_decode_attention
            hidden, new, _ = self._paged_stack(
                self._embed(Tensor(toks[:, None])), lens,
                (k_pools, v_pools, k_scales, v_scales),
                lambda kp, vp, k, v, **scales: paged_decode_write(
                    kp, vp, tables, lens, k[:, 0], v[:, 0], active,
                    **scales),
                lambda q, kp, vp, **scales: attention(
                    q[:, 0], kp, vp, tables,
                    jnp.where(active, lens + 1, lens), kernel_mode=mode,
                    **scales))
            nxt = self._next_token(hidden, lambda logits: logits[:, 0],
                                   key, temp)
            return (nxt, *new)
        return self._as_program(body, "llama.paged_decode", 2, quantized, mode)

    # -- self-speculative decode (docs/SERVING.md "Decode speed tiers") --

    def _build_spec(self, quantized, mode):
        """The speculative VERIFY program: one batched multi-position
        sweep over every live slot. For slot ``b``, input positions
        ``seq_lens[b] + i`` carry ``toks[b, i]`` (the last emitted
        token, then the proposed drafts); each position's KV is written
        (rows past ``n_inputs[b]`` masked to the null block) and its
        query attends the whole paged context causally by absolute
        position — so ``out[b, i]`` is exactly the greedy token a
        sequential decode would emit after consuming input ``i``.
        Greedy only (the scheduler gates speculation on temperature 0);
        host-side acceptance decides how many rows survive."""
        def body(toks, lens, n_inputs, active, tables, k_pools, v_pools,
                 k_scales, v_scales):
            from ..inference.paged import (paged_spec_attention_dense,
                                           paged_spec_write)
            hidden, new, _ = self._paged_stack(
                self._embed(Tensor(toks)), lens,
                (k_pools, v_pools, k_scales, v_scales),
                lambda kp, vp, k, v, **scales: paged_spec_write(
                    kp, vp, tables, lens, k, v, n_inputs, active,
                    **scales),
                lambda q, kp, vp, **scales: paged_spec_attention_dense(
                    q, kp, vp, tables, lens, active, **scales))
            return (self._next_token(hidden, lambda logits: logits),
                    *new)
        return self._as_program(body, "llama.paged_spec", 6, quantized)

    def paged_spec_step(self, cache, last_tokens, draft_tokens, n_inputs,
                        active):
        """Speculative verify sweep: write the KV of ``1 + k``
        candidate tokens per active slot (``last_tokens[b]`` then
        ``draft_tokens[b]``) at positions ``seq_lens[b] ..`` and return
        [B, 1 + k] greedy next tokens (a device array: reading it is what
        waits for the sweep) — ``out[b, i]`` is the token
        sequential greedy decode would emit after consuming input
        ``i``. ``n_inputs[b]`` (= 1 + real drafts) masks padding
        writes. Pools update in place; ``seq_lens`` do NOT advance —
        the caller (scheduler ``_decode_spec``) accepts the longest
        matching prefix and rolls rejected rows back."""
        toks = np.concatenate(
            [np.asarray(last_tokens).reshape(-1, 1),
             np.asarray(draft_tokens)], axis=1)
        with self._paged_call(cache, "spec") as (call, _):
            nxt, = call(
                (jnp.asarray(toks, jnp.int32), jnp.asarray(cache.seq_lens),
                 jnp.asarray(n_inputs, jnp.int32), jnp.asarray(active),
                 cache.block_tables))
        return nxt

    def forward_hidden(self, input_ids, kv_sink=None):
        """Decoder stack output (post final RMSNorm), before the head."""
        from ..kernels.on_mesh import kernel_mesh
        mesh = self.serving_mesh()
        # a mesh-served model's attention kernel runs per head shard
        with kernel_mesh(mesh.jax_mesh if mesh is not None else None):
            x = self._embed(input_ids)
            for block in self.layers:
                x = block(x, kv_sink=kv_sink)
            with _scope("residual"):
                return self.norm(x)

    def loss(self, input_ids, labels):
        if self.config.fused_head_ce:
            x = self.forward_hidden(input_ids)
            with _scope("head"):
                tied = self.lm_head is None
                w = self.embed_tokens.weight if tied \
                    else self.lm_head.weight
                return F.fused_linear_cross_entropy(
                    x[:, :-1, :], w, labels[:, 1:], transpose_weight=tied)
        logits = self(input_ids)
        with _scope("head"):
            return F.cross_entropy(logits[:, :-1, :], labels[:, 1:])

    def flops_per_token(self, seq_len):
        n = self.num_params()
        l, d = self.config.num_layers, self.config.hidden_size
        return 6 * n + 12 * l * d * seq_len

    @staticmethod
    def tp_placement_rules(mesh, tp_axis="tp"):
        """Megatron-style TP placements (reference mp_layers.py:47,334,541:
        column-parallel q/k/v/gate/up, row-parallel o/down, vocab-parallel
        embedding) as rules for distributed.apply_placement_rules."""
        from ..distributed import Replicate, Shard
        axis = mesh.dim_names.index(tp_axis)

        def P(*pairs):
            pl = [Replicate()] * mesh.ndim
            for mesh_dim, tensor_dim in pairs:
                pl[mesh_dim] = Shard(tensor_dim)
            return pl

        col = P((axis, 1))   # [in, out] split out
        row = P((axis, 0))   # [in, out] split in
        return [
            ("q_proj.weight", col), ("k_proj.weight", col),
            ("v_proj.weight", col), ("gate_proj.weight", col),
            ("up_proj.weight", col),
            ("o_proj.weight", row), ("down_proj.weight", row),
            ("embed_tokens.weight", P((axis, 0))),  # vocab-parallel
            ("lm_head.weight", col),
        ]
