"""train loop: model FLOP utilization, percent of the chip's bf16 peak.

Operations the forward and backward passes REQUIRE per token: 6 x the
parameters that multiply (every layer's projections and MLP, and the
output head; the embedding is a lookup) plus causal attention, 6*S*H*D a
layer.  Rematerialized forward passes are not counted."""

from benchmark import cells


def flops_per_token(cfg) -> float:
    d = cells.architecture(cfg).dims_of(cfg)
    attn_w = d["hidden"] * d["head_dim"] * (2 * d["heads"] + 2 * d["kv_heads"])
    mlp_w = 3 * d["hidden"] * d["mlp"]
    matmul = d["layers"] * (attn_w + mlp_w) + d["hidden"] * d["vocab"]
    seq = int(cfg["trainer"]["seq_len"])
    attention = d["layers"] * 6.0 * seq * d["heads"] * d["head_dim"]
    return 6.0 * matmul + attention


def read(name, ctx):
    if ctx["peaks"] is None:
        return None
    rate = ctx["e2e"]["train_tokens_per_s"]
    return 100.0 * rate * flops_per_token(ctx["cell"].config) / (
        ctx["peaks"]["bf16_flops"] * ctx["cell"].chips
    )
