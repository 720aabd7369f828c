"""Model FLOP/s utilization of the free-running part of a traced window:
tokens per second times the FLOPs a token needs
(``ops_count.train_flops_per_token``) over the chip's bf16 peak."""

from benchmarks import ops_count


def read(ctx):
    if ctx["peaks"] is None:
        return None
    per_token = ops_count.train_flops_per_token(
        ctx["params"], ctx["num_layers"], ctx["hidden_size"], ctx["seq_len"])
    return ops_count.mfu_percent(ctx["train_tok_s"], per_token,
                                 ctx["peaks"]["bf16_flops_per_s"],
                                 ctx["cell"].chips)
