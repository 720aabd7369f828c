"""Operations and bytes of a hybrid state-space / attention decoder
(``jamba``), computed from the configuration's shapes and kept with the
benchmark so that no change to the program moves them. Useful work only:
what the algorithm needs, each array once.

Per state-space layer the recurrence is, a channel ``e`` of ``E`` and a
state ``n`` of ``N``: ``h <- exp(delta A) h + (delta c) B`` (a product
for the exponent, the exponential, two products and a sum) and ``y +=
h C`` (a product and a sum): 7 operations an element of ``h``.
"""

from __future__ import annotations

_STATE_BYTES = 4       # h is float32
_OPS_PER_STATE = 7


def shapes(config):
    """The sizes the counts below need, from a configuration file's
    keys."""
    d = int(config["hidden_size"])
    layers = int(config["num_hidden_layers"])
    attn = sum(1 for i in range(layers)
               if i % int(config["attn_layer_period"])
               == int(config["attn_layer_offset"]))
    heads = int(config["num_attention_heads"])
    return {"hidden": d, "layers": layers, "attn_layers": attn,
            "state_layers": layers - attn,
            "channels": int(config["mamba_expand"]) * d,
            "states": int(config["mamba_d_state"]),
            "rank": int(config["mamba_dt_rank"]),
            "taps": int(config["mamba_d_conv"]),
            "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": d // heads,
            "ffn": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"]),
            "tied": bool(config["tie_word_embeddings"])}


def state_update_bytes(config, slots):
    """Bytes one ``ssm_update`` call (one layer, ``slots`` live slots)
    must move: every slot's ``h`` read and written once, and the call's
    per-slot inputs and output (delta, c, y [E] float32; B, C [N])."""
    s = shapes(config)
    e, n = s["channels"], s["states"]
    return slots * (2 * e * n * _STATE_BYTES + 3 * e * 4 + 2 * n * 4)


def state_update_flops(config, slots):
    s = shapes(config)
    return slots * s["channels"] * s["states"] * _OPS_PER_STATE


def scan_bytes_per_token(config, act_bytes=2):
    """Bytes a true token costs one ``ssm_scan`` call (one layer): the
    kernel's inputs and outputs once (delta before its softplus [E]
    float32; c and y [E] in the activations' type; B, C [N] float32).
    The state itself is read and written once a sequence, not a token."""
    s = shapes(config)
    return s["channels"] * (4 + 2 * act_bytes) + 2 * s["states"] * 4


def scan_flops_per_token(config):
    s = shapes(config)
    return s["channels"] * s["states"] * _OPS_PER_STATE


def matmul_params(config):
    """Parameters one row of activations multiplies in a whole forward:
    every matrix of every layer and the head; norms, biases and the
    convolution's taps are not matrices."""
    s = shapes(config)
    d, e = s["hidden"], s["channels"]
    mixer = d * 2 * e + e * (s["rank"] + 2 * s["states"]) \
        + s["rank"] * e + e * d
    attention = d * s["heads"] * s["head_dim"] * 2 \
        + d * s["kv_heads"] * s["head_dim"] * 2
    ffn = 3 * d * s["ffn"]
    return s["state_layers"] * mixer + s["attn_layers"] * attention \
        + s["layers"] * ffn + s["vocab"] * d


def param_bytes(config, weight_bytes=2):
    """Bytes of all parameters as served: the matrices, the embedding
    (the head itself where they are tied), and the state-space layers'
    ``A_log`` [E, N], convolution, ``D`` and biases."""
    s = shapes(config)
    e = s["channels"]
    small = s["state_layers"] * (e * s["states"] + s["taps"] * e + 3 * e)
    embedding = 0 if s["tied"] else s["vocab"] * s["hidden"]
    return (matmul_params(config) + small + embedding) * weight_bytes


def decode_step_flops(config, rows, context_tokens):
    """Useful FLOPs of one decode step: ``rows`` live slots through
    every matrix (2 a parameter a row), the recurrence, and attention
    over ``context_tokens`` keys and values in all (summed over the
    slots): q.k and p.v, 2 FLOPs each a head feature."""
    s = shapes(config)
    attention = 4 * context_tokens * s["heads"] * s["head_dim"] \
        * s["attn_layers"]
    return 2 * rows * matmul_params(config) \
        + s["state_layers"] * state_update_flops(config, rows) + attention


def decode_step_bytes(config, rows, context_tokens, weight_bytes=2,
                      kv_bytes=2):
    """Useful bytes of one decode step: the weights once, the live
    slots' state read and written, the context's K and V once."""
    s = shapes(config)
    kv = context_tokens * 2 * s["kv_heads"] * s["head_dim"] * kv_bytes \
        * s["attn_layers"]
    return param_bytes(config, weight_bytes) \
        + s["state_layers"] * state_update_bytes(config, rows) + kv
