"""Operations and bytes a call needs, worked out from its shapes, and the
utilizations that follow. The yardstick for ``mfu`` and ``*_roofline``:
kept here so that no change to the program can move it.
"""

from __future__ import annotations


def train_flops_per_token(n_params, num_layers, hidden_size, seq_len):
    """Forward + backward FLOPs a token of a dense decoder needs: 6 per
    parameter that multiplies (all but the position table) plus the
    attention scores and values, 12 * layers * hidden * seq_len
    (PaLM appendix B; copied from ``models.gpt.GPT.flops_per_token``).
    Recomputation is not counted."""
    return 6.0 * n_params + 12.0 * num_layers * hidden_size * seq_len


def mfu_percent(tokens_per_s, flops_per_token, peak_flops_per_s, chips=1):
    return 100.0 * tokens_per_s * flops_per_token / (peak_flops_per_s * chips)


def flash_causal_flops(batch, heads, seq_len, head_dim):
    """(forward, backward) FLOPs of causal flash attention over one
    [batch, heads, seq_len, head_dim] call. A matmul of the score shape
    is 2*b*h*s*s*d; causality halves it. Forward needs two (QK^T, PV),
    backward five (QK^T again, dO V^T, P^T dO, dS^T Q, dS K). A kernel
    split into dq and dkv passes computes the first two of those twice;
    that is its choice and is not counted. Head sizes the kernel pads to
    are not counted either."""
    matmul = 2.0 * batch * heads * seq_len * seq_len * head_dim * 0.5
    return 2.0 * matmul, 5.0 * matmul


def flash_bytes(batch, heads, seq_len, head_dim, itemsize=2):
    """(forward, backward) bytes the call has to move: forward reads q,
    k, v and writes o; backward reads q, k, v, o, dO and writes dq, dk,
    dv. The float32 row statistics are a 1/head_dim of that and are left
    out."""
    tensor = float(batch * heads * seq_len * head_dim * itemsize)
    return 4.0 * tensor, 8.0 * tensor


def roofline_percent(flops, nbytes, seconds, peak):
    """Share of the roofline reached, and which bound binds: the least
    time the chip could take (the larger of flops over peak FLOP/s and
    bytes over peak bytes/s) over the time taken."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    least = max(t_compute, t_memory)
    return (100.0 * least / seconds,
            "compute" if t_compute >= t_memory else "memory")
