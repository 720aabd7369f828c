"""GPT-2 family.

Capability parity with the reference's GPT workloads (PaddleNLP GPT trained
through paddle.nn / fleet; in-repo analogues: the transformer layers of
`python/paddle/nn/layer/transformer.py` and the semi_auto_parallel llama/gpt
tests under `test/auto_parallel/hybrid_strategy/`). TPU-first choices:
- pre-LN residual blocks, learned positional embeddings (GPT-2);
- attention through F.scaled_dot_product_attention → Pallas flash kernel;
- a single weight-tied [vocab, d] embedding used for both lookup and the
  LM head matmul (one big MXU matmul, bf16-friendly);
- no data-dependent python control flow — the whole forward traces into
  one XLA program.
"""

from __future__ import annotations

import dataclasses
import math

from .. import nn
from ..nn import functional as F
from ..profiler.tracing import scope as _scope


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304  # padded to a 128-multiple for the MXU
    max_position_embeddings: int = 1024
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = None
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    use_fp8: bool = False  # fp8 block linears (amp.fp8 delayed scaling)
    # loss() computes CE through the blockwise fused LM-head
    # (F.fused_linear_cross_entropy) instead of materializing [b,s,V]
    # logits — the c_softmax_with_cross_entropy-class fusion
    fused_head_ce: bool = True

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size

    @staticmethod
    def gpt2_small():
        return GPTConfig(hidden_size=768, num_layers=12, num_heads=12)

    @staticmethod
    def gpt2_medium():  # the 345M PR1 reference config
        return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16)

    @staticmethod
    def gpt2_large():
        return GPTConfig(hidden_size=1280, num_layers=36, num_heads=20)

    @staticmethod
    def tiny():  # test-sized
        return GPTConfig(vocab_size=256, max_position_embeddings=64,
                         hidden_size=64, num_layers=2, num_heads=4)


def _normal_attr(std):
    return nn.ParamAttr(initializer=nn.initializer.Normal(0.0, std))


class GPTAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        d, h = config.hidden_size, config.num_heads
        self.num_heads = h
        self.head_dim = d // h
        std = config.initializer_range
        proj_std = std / math.sqrt(2 * config.num_layers)
        self.qkv_proj = nn.Linear(d, 3 * d, weight_attr=_normal_attr(std))
        self.out_proj = nn.Linear(d, d, weight_attr=_normal_attr(proj_std))
        self.dropout = config.dropout

    def forward(self, x):
        from .. import ops
        b, s, d = x.shape
        qkv = self.qkv_proj(x)
        qkv = ops.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q, k, v = ops.unbind(qkv, axis=2)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            dropout_p=self.dropout if self.training else 0.0)
        out = ops.reshape(out, [b, s, d])
        return self.out_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        d = config.hidden_size
        std = config.initializer_range
        proj_std = std / math.sqrt(2 * config.num_layers)
        self.fc_in = nn.Linear(d, config.intermediate_size,
                               weight_attr=_normal_attr(std))
        self.fc_out = nn.Linear(config.intermediate_size, d,
                                weight_attr=_normal_attr(proj_std))

    def forward(self, x):
        return self.fc_out(F.gelu(self.fc_in(x), approximate=True))


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)
        self.dropout = nn.Dropout(config.dropout)

    def forward(self, x):
        with _scope("residual"):
            h = self.ln_1(x)
        with _scope("attn"):
            out = self.attn(h)
        with _scope("residual"):
            x = x + self.dropout(out)
            h = self.ln_2(x)
        with _scope("ffn"):
            out = self.mlp(h)
        with _scope("residual"):
            return x + self.dropout(out)


class GPT(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        std = config.initializer_range
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size,
                                weight_attr=_normal_attr(std))
        self.wpe = nn.Embedding(config.max_position_embeddings,
                                config.hidden_size,
                                weight_attr=_normal_attr(std))
        self.drop = nn.Dropout(config.dropout)
        self.h = nn.LayerList([GPTBlock(config)
                               for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_epsilon)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     weight_attr=_normal_attr(std),
                                     bias_attr=False)
        else:
            self.lm_head = None
        if config.use_fp8:
            # block linears in fp8; the LM head stays bf16 (loss fidelity,
            # the standard fp8-transformer recipe)
            from ..amp.fp8 import convert_to_fp8
            convert_to_fp8(self, exclude=("lm_head",))

    def forward_hidden(self, input_ids):
        """Transformer stack output (post ln_f), before the LM head."""
        from .. import ops
        b, s = input_ids.shape
        with _scope("residual"):
            pos = ops.arange(0, s, dtype="int64")
            x = self.wte(input_ids) + self.wpe(pos)
            x = self.drop(x)
        for block in self.h:
            x = block(x)
        with _scope("residual"):
            return self.ln_f(x)

    def forward(self, input_ids):
        from .. import ops
        x = self.forward_hidden(input_ids)
        with _scope("head"):
            if self.lm_head is not None:
                return self.lm_head(x)
            # weight-tied head: [b,s,d] @ [d,vocab]
            return ops.matmul(x, self.wte.weight, transpose_y=True)

    def loss(self, input_ids, labels):
        """Next-token cross entropy; labels already shifted or equal to
        input_ids (we shift internally)."""
        if self.config.fused_head_ce:
            # blockwise head+CE: the [b,s,V] logits never materialize
            x = self.forward_hidden(input_ids)
            with _scope("head"):
                tied = self.lm_head is None
                w = self.wte.weight if tied else self.lm_head.weight
                return F.fused_linear_cross_entropy(
                    x[:, :-1, :], w, labels[:, 1:], transpose_weight=tied)
        logits = self(input_ids)
        with _scope("head"):
            shift_logits = logits[:, :-1, :]
            shift_labels = labels[:, 1:]
            return F.cross_entropy(shift_logits, shift_labels)

    def num_params(self, non_embedding=True):
        n = sum(p.size for p in self.parameters())
        if non_embedding:
            n -= self.wpe.weight.size
        return n

    def flops_per_token(self, seq_len):
        """Approximate training FLOPs/token (fwd+bwd), PaLM-style 6N + attn."""
        n = self.num_params()
        l, d = self.config.num_layers, self.config.hidden_size
        return 6 * n + 12 * l * d * seq_len

    @staticmethod
    def tp_placement_rules(mesh, tp_axis="tp"):
        """Megatron TP placements (see Llama.tp_placement_rules)."""
        from ..distributed import Replicate, Shard
        axis = mesh.dim_names.index(tp_axis)

        def P(*pairs):
            pl = [Replicate()] * mesh.ndim
            for mesh_dim, tensor_dim in pairs:
                pl[mesh_dim] = Shard(tensor_dim)
            return pl

        return [
            ("qkv_proj.weight", P((axis, 1))),
            ("qkv_proj.bias", P((axis, 0))),
            ("out_proj.weight", P((axis, 0))),
            ("fc_in.weight", P((axis, 1))),
            ("fc_in.bias", P((axis, 0))),
            ("fc_out.weight", P((axis, 0))),
            ("wte.weight", P((axis, 0))),  # vocab-parallel
        ]
