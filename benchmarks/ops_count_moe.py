"""Operations and bytes of the sparse-expert, block-diffusion serving
step, worked out from the configuration and what the program counted.
The yardstick of ``moe_gmm_hbm_roofline``, ``block_attn_hbm_roofline``
and ``step_mfu``: kept here so that no change to the program can move it.
``fields`` is the configuration file's (Hugging Face names).
"""

from __future__ import annotations


def expert_layer_bytes(experts_hit, rows, fields, itemsize):
    """Bytes one layer's grouped expert matmuls have to move: the three
    matrices of every expert that got a row, and each row's activations
    in and out of the two calls (hidden in and width out of gate/up,
    width in and hidden out of down). Useful bytes only: the padding of
    a group to whole tiles is the kernel's choice and not counted."""
    hidden = fields["hidden_size"]
    width = fields["moe_intermediate_size"]
    weights = experts_hit * 3.0 * hidden * width * itemsize
    activations = rows * 2.0 * (hidden + width) * itemsize
    return weights + activations


def attention_bytes(context_tokens, fields, itemsize):
    """Bytes one layer's paged attention has to read: K and V of every
    context token (the block's own rows among them)."""
    return (context_tokens * 2.0 * fields["num_key_value_heads"]
            * fields["head_dim"] * itemsize)


def params_a_row_multiplies(fields):
    """(per layer, head): the parameters one row of a forward is
    multiplied with: q, k, v, o, the router over all experts, the
    ``num_experts_per_tok`` experts it is routed to; and the untied
    head. Embedding rows are looked up, not multiplied."""
    hidden = fields["hidden_size"]
    heads = fields["num_attention_heads"] * fields["head_dim"]
    kv = fields["num_key_value_heads"] * fields["head_dim"]
    layer = (2 * hidden * heads + 2 * hidden * kv
             + hidden * fields["num_experts"]
             + fields["num_experts_per_tok"] * 3 * hidden
             * fields["moe_intermediate_size"])
    return layer, hidden * fields["vocab_size"]


def block_step_flops(rows, context_tokens, fields):
    """Useful FLOPs of one block step over ``rows`` positions (slots x
    block length) whose slots hold ``context_tokens`` keys in all: 2 a
    parameter a row multiplies, and the attention's scores and values
    (2 products of 2 FLOPs x query heads x head size a row a key: a
    slot's L rows each see its whole context, so rows x keys is
    block length x context tokens)."""
    layer, head = params_a_row_multiplies(fields)
    layers = fields["num_hidden_layers"]
    matmul = 2.0 * rows * (layers * layer + head)
    attention = (4.0 * fields["num_attention_heads"] * fields["head_dim"]
                 * fields["block_length"] * context_tokens * layers)
    return matmul + attention
