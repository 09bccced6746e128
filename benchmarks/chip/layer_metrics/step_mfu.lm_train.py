"""The whole LM training step's share of the chip's peak: the FLOPs one
sequence requires (forward, activation backward and the adapters' weight
gradients, ``chipbench.counts_lm``) times the sequences trained per second
over the traced window, over peak x chips.  Sampling, KL, Adam, consensus
and recomputation are not counted."""
from chipbench import counts_lm


def reduce(ctx):
    cfg = ctx["cfg"]
    seq = cfg["data"]["dataset_params"]["seq_len"]
    flops = seq * counts_lm.train_flops_per_token(
        cfg, seq, cfg["inference"]["lora_rank"])
    peak = ctx["peaks"]["flops_bf16_per_s"] * ctx["chips"]
    return 100.0 * ctx["window"]["samples_per_s"] * flops / peak
