"""The routed experts' share of their compute roofline: the FLOPs the
routed (token, expert) pairs of a round require, 2 x (2 x 3 x d x f) per
pair (forward and activation backward, three d x f matmuls each), over the
bf16 peak, divided by the device time under ``moe/experts`` per round (the
grouped matmuls with their sort, gathers and combine, and the
recomputation of the backward pass)."""
from chipbench import counts_lm


def reduce(ctx):
    s = ctx.get("lm", {}).get("experts_s")
    if not s:
        return None
    cfg = ctx["cfg"]
    flops = counts_lm.pairs_per_round(cfg) * counts_lm.expert_pair_flops(cfg)
    return 100.0 * flops / ctx["peaks"]["flops_bf16_per_s"] / s
