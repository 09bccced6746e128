"""The whole training step's share of the chip's peak: the MLP's forward
and backward matmul FLOPs per trained row and MC sample, times the rows
trained per second over the traced window, over peak x chips.  Sampling,
KL, Adam and consensus are not counted: the model does not require them."""
from chipbench import counts


def reduce(ctx):
    m = ctx["cfg"]["model"]
    sizes = [m["input_dim"]] + [m["hidden"]] * m["depth"] + [m["n_classes"]]
    flops = counts.mlp_train_flops_per_row(
        sizes, ctx["cfg"]["inference"]["n_mc_samples"])
    peak = ctx["peaks"]["flops_bf16_per_s"] * ctx["chips"]
    return 100.0 * ctx["window"]["samples_per_s"] * flops / peak
