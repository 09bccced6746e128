"""The network consensus kernel's share of its roofline: the least time
the chip needs for what eq. (6) requires over [N, P] (16 B per agent and
parameter plus W's nonzeros, 4 FLOPs per nonzero and parameter), over the
kernel's device time per round.  Padding and dense-W products over zeros
are not work."""
from chipbench import cells, counts

KERNEL = ("consensus_fused_network",)


def reduce(ctx):
    s = ctx["reduced"].kernel_s(KERNEL)
    if s is None:
        return None
    cfg = ctx["cfg"]
    ref = cells.reference_module(cfg)
    rows = ref.graph_rows(cfg["topology"])
    nnz = sum(len(r) for r in rows)
    p = counts.mlp_params(ref.layer_sizes(cfg["model"]))
    t_min, _ = counts.roofline_seconds(
        counts.eq6_flops(nnz, p), counts.eq6_bytes(len(rows), p, nnz),
        ctx["peaks"])
    return 100.0 * t_min / (s / ctx["window"]["rounds"])
