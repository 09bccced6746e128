"""Operations and bytes that the work *requires*, computed from shapes.

These count what the mathematics needs, whatever implements it: padding,
dense-W products over zeros, recomputation and sampling are not work.
"""
from __future__ import annotations


def mlp_matmul_weights(sizes) -> int:
    """Weight entries of the MLP's matmuls (784-200-200-10: 198,800)."""
    return sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))


def mlp_train_flops_per_row(sizes, mc_samples: int = 1) -> int:
    """Forward plus backward matmul FLOPs for one row and one MC sample:
    2 per multiply-add forward, twice that backward (activations and
    weights), so 3 x 2 x weight entries."""
    return 3 * 2 * mlp_matmul_weights(sizes) * mc_samples


def mlp_params(sizes) -> int:
    """P: weights plus biases (784-200-200-10: 199,210)."""
    return mlp_matmul_weights(sizes) + sum(sizes[1:])


def eq6_flops(nnz_w: int, n_params: int) -> int:
    """Eq. (6) over [N, P]: per nonzero W_ij and parameter, a multiply-add
    into the precision sum and one into the precision-weighted mean sum."""
    return 4 * nnz_w * n_params


def eq6_bytes(n_agents: int, n_params: int, nnz_w: int,
              w_bytes: int = 4) -> int:
    """Read mean and rho, write mean and rho, fp32 (16 B per agent and
    parameter), plus W's nonzero values once."""
    return 16 * n_agents * n_params + w_bytes * nnz_w


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds, which bound) on a chip with these peaks."""
    t_c = flops / peaks["flops_bf16_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_m, "memory") if t_m >= t_c else (t_c, "compute")
