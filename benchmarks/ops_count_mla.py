"""Operations and bytes of a decoder with latent attention (MLA),
sigmoid-routed experts beside a shared expert and hyper-connection
residual streams (``xing4_0``), computed from the configuration's shapes
and kept with the benchmark so that no change to the program moves them.
Useful work only: what the algorithm needs, each array once. ``fields``
is the configuration file's (Hugging Face names).

The decode step runs latent attention in its absorbed form: a head's
query meets the cached row as it lies, ``latent + rope`` values for the
score and ``latent`` for the output, so a context token costs a layer
``2 H (latent + rope) + 2 H latent`` FLOPs and ``(latent + rope) x item
size`` bytes, whatever the number of heads reads it. ``kv_b_proj`` is
multiplied with each row either way (folded into the query and the
output), so the parameters a row multiplies are the published ones.
"""

from __future__ import annotations


def shapes(fields):
    """The sizes the counts below need."""
    layers = int(fields["num_hidden_layers"])
    dense = min(int(fields["first_k_dense_replace"]), layers)
    return {"hidden": int(fields["hidden_size"]), "layers": layers,
            "dense_layers": dense, "sparse_layers": layers - dense,
            "heads": int(fields["num_attention_heads"]),
            "q_rank": int(fields["q_lora_rank"]),
            "latent": int(fields["kv_lora_rank"]),
            "nope": int(fields["qk_nope_head_dim"]),
            "rope": int(fields["qk_rope_head_dim"]),
            "v": int(fields["v_head_dim"]),
            "ffn": int(fields["intermediate_size"]),
            "width": int(fields["moe_intermediate_size"]),
            "experts": int(fields["n_routed_experts"]),
            "top_k": int(fields["num_experts_per_tok"]),
            "shared": int(fields["n_shared_experts"]),
            "streams": int(fields["hc_mult"]),
            "vocab": int(fields["vocab_size"])}


def mla_params(fields):
    """Parameters of one layer's latent attention: the query's down and
    up projections, the row's down projection, ``kv_b_proj`` and the
    output projection (28.41M at the published widths)."""
    s = shapes(fields)
    d, h = s["hidden"], s["heads"]
    return (d * s["q_rank"] + s["q_rank"] * h * (s["nope"] + s["rope"])
            + d * (s["latent"] + s["rope"])
            + s["latent"] * h * (s["nope"] + s["v"]) + h * s["v"] * d)


def stream_map_params(fields):
    """Parameters of one layer's stream maps: two sublayers, each
    ``n d x n (n + 2)``."""
    s = shapes(fields)
    n = s["streams"]
    return 2 * n * s["hidden"] * n * (n + 2)


def expert_params(fields):
    """One expert's three matrices."""
    s = shapes(fields)
    return 3 * s["hidden"] * s["width"]


def attention_flops_per_token(fields):
    """FLOPs a context token costs one layer's absorbed attention (69.6
    kFLOP at 32 heads of 512 + 64)."""
    s = shapes(fields)
    return 2 * s["heads"] * (s["latent"] + s["rope"]) \
        + 2 * s["heads"] * s["latent"]


def attention_bytes_per_token(fields, itemsize=2):
    """Bytes a context token costs one layer's decode attention: its
    cached row, once (1152 B at 512 + 64 in bfloat16)."""
    s = shapes(fields)
    return (s["latent"] + s["rope"]) * itemsize


def attention_call_bytes(fields, context_tokens, itemsize=2):
    """Bytes one ``mla_decode`` call (one layer) must read."""
    return context_tokens * attention_bytes_per_token(fields, itemsize)


def params_a_row_multiplies(fields):
    """(a dense layer, a sparse layer, the head): attention, the stream
    maps, and the dense SwiGLU; or the router over all experts, the
    ``num_experts_per_tok`` routed experts and the shared ones. The
    embedding's rows are looked up, not multiplied."""
    s = shapes(fields)
    d = s["hidden"]
    common = mla_params(fields) + stream_map_params(fields)
    dense = common + 3 * d * s["ffn"]
    sparse = common + d * s["experts"] \
        + (s["top_k"] + s["shared"]) * expert_params(fields)
    return dense, sparse, d * s["vocab"]


def decode_step_flops(fields, rows, context_tokens):
    """Useful FLOPs of one decode step: ``rows`` live slots through the
    parameters a row multiplies (2 a parameter a row), and the absorbed
    attention over ``context_tokens`` cached rows in all (summed over
    the slots), every layer."""
    s = shapes(fields)
    dense, sparse, head = params_a_row_multiplies(fields)
    return 2.0 * rows * (s["dense_layers"] * dense
                         + s["sparse_layers"] * sparse + head) \
        + attention_flops_per_token(fields) * context_tokens * s["layers"]


def decode_step_bytes(fields, context_tokens, experts_hit, itemsize=2):
    """Useful bytes of one decode step: the weights once (the head, each
    layer's attention and stream maps, the dense SwiGLU, each sparse
    layer's router, shared expert and the ``experts_hit`` experts a
    sparse layer that got a row) and the context's cached rows once a
    layer."""
    s = shapes(fields)
    d = s["hidden"]
    common = mla_params(fields) + stream_map_params(fields)
    weights = (d * s["vocab"] + s["layers"] * common
               + s["dense_layers"] * 3 * d * s["ffn"]
               + s["sparse_layers"] * (
                   d * s["experts"]
                   + (s["shared"] + experts_hit) * expert_params(fields)))
    return weights * itemsize + attention_bytes_per_token(
        fields, itemsize) * context_tokens * s["layers"]
