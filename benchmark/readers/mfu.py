"""Model FLOP/s utilisation: tokens/s/chip times the FLOPs a token requires,
over the chip's bf16 peak.

Required FLOPs per token: 6*N for the weights' forward and backward, plus
causal attention counted once, 6*L*H*S (half of the full square's 12*L*H*S that
``TransformerConfig.flops_per_token`` charges), recomputation not counted.
"""


def read(ctx):
    c, peak = ctx["counters"], ctx["peak"]
    if peak is None:
        return None
    per_token = 6 * c["n_params"] + 6 * c["n_layers"] * c["hidden"] * c["seq_len"]
    return 100.0 * c["tokens_per_s_per_chip"] * per_token / peak["bf16_flops_per_s"]
