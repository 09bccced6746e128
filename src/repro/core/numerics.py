"""Shared numerical primitives for Gaussian posteriors.

Single home for the softplus/softplus^-1 pair so the Pallas kernels, the
pure-jnp reference paths, and the core posterior code all use the SAME
stable formulation (previously the kernel inlined its own copy — satellite
fix of ISSUE 1).

``softplus_inv`` is stable over the full fp32 range of sigma:

* tiny y (sigma -> 0): softplus_inv(y) = log(expm1(y)) ~= log(y); the naive
  ``y + log1p(-exp(-y))`` form computes log1p(-exp(-eps)) which underflows
  ``-exp(-y)`` to -1 and returns -inf one ulp too early.  Below y = 0.25
  we use ``log(y * (expm1(y) / y))`` with the Taylor series of
  ``expm1(y) / y``, which keeps full precision down to y ~ 1e-38.
* huge y (sigma >> 1): exp(-y) underflows to 0 and the result is exactly y,
  which is the correct asymptote (softplus(x) -> x for large x).

The jnp form avoids ``jnp.expm1`` (``softplus_inv_py`` keeps
``math.expm1``): Mosaic (the TPU Pallas compiler) has no
lowering for it, and the kernels and the XLA reference must share one
formulation to agree bitwise.  It also avoids Kahan's ``(u - 1) * x /
log(u)`` with ``u = exp(x)``: XLA's algebraic simplifier rewrites
``log(exp(x))`` to ``x``, which turns that back into the inaccurate
``exp(x) - 1``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# canonical compute dtype for flat posterior buffers and kernel wrappers
COMPUTE_DTYPE = jnp.float32

# Precision of every eq. (6) contraction (W @ prec, W @ prec*mu).  A TPU
# runs an f32 matmul at DEFAULT precision as one bf16 pass, which would
# round W and the exchanged statistics to 8 mantissa bits whatever the wire
# dtype says; HIGHEST keeps the contraction fp32.  The CPU backend computes
# f32 dots in full precision either way, so CPU results are unchanged.
EXCHANGE_PRECISION = jax.lax.Precision.HIGHEST

# -- wire-dtype compression (ROADMAP "Wire precision") ----------------------
#
# The consensus round exchanges the sufficient statistics (prec, prec*mu);
# on the wire-bound paths those may travel compressed.  Contract: cast to
# the wire dtype AT THE EXCHANGE BOUNDARY, accumulate in fp32.  "f32" is a
# STRUCTURAL no-op — every helper below returns its input unchanged, so the
# f32 path emits the identical computation graph (bitwise identity with the
# pre-wire kernels, pinned by tests/test_wire_dtype.py).

WIRE_DTYPES = {
    "f32": jnp.float32,
    "bf16": jnp.bfloat16,
    "f16": jnp.float16,
}

# unit roundoff u = eps/2 of round-to-nearest into the wire dtype: one
# cast perturbs each exchanged scalar by a relative error <= u.  The
# analytic error bound of a wire-compressed consensus output derives from
# u alone (tests/test_wire_dtype.py): new_prec is a convex combination of
# positive rounded terms (relative error <= u), new_pm accumulates
# |pm|-weighted roundoff, and the fp32 accumulation adds only O(eps_f32).
WIRE_UNIT_ROUNDOFF = {
    "f32": 0.0,
    "bf16": 2.0 ** -8,  # bf16: 7 stored mantissa bits, eps = 2^-7
    "f16": 2.0 ** -11,  # f16: 10 stored mantissa bits, eps = 2^-10
}


def canonical_wire_dtype(wire_dtype):
    """Normalize a wire-dtype spec (``None`` | ``"f32"|"bf16"|"f16"`` | a
    dtype-like) to the jnp dtype.  ``None`` means uncompressed (f32).
    Dtype-likes outside the supported wire set are rejected exactly like
    their string spellings (an int or f64 wire would silently corrupt the
    exchanged statistics instead of compressing them)."""
    if wire_dtype is None:
        return jnp.float32
    if isinstance(wire_dtype, str):
        if wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"unknown wire_dtype {wire_dtype!r}; known: "
                f"{sorted(WIRE_DTYPES)}"
            )
        return WIRE_DTYPES[wire_dtype]
    dt = jnp.dtype(wire_dtype)
    for cand in WIRE_DTYPES.values():
        if dt == jnp.dtype(cand):
            return cand
    raise ValueError(
        f"unsupported wire_dtype {wire_dtype!r}; known: "
        f"{sorted(WIRE_DTYPES)} (or their dtypes)"
    )


def wire_dtype_name(wire_dtype) -> str:
    """The spec-string name of a wire dtype (inverse of
    ``canonical_wire_dtype``)."""
    dt = canonical_wire_dtype(wire_dtype)
    for name, cand in WIRE_DTYPES.items():
        if jnp.dtype(cand) == jnp.dtype(dt):
            return name
    raise ValueError(f"{wire_dtype!r} is not a supported wire dtype")


def wire_itemsize(wire_dtype) -> int:
    """Bytes per exchanged scalar at this wire dtype (cost-model input)."""
    return jnp.dtype(canonical_wire_dtype(wire_dtype)).itemsize


def wire_error_bound(wire_dtype) -> float:
    """Unit roundoff u of one cast into the wire dtype (0.0 for f32) — the
    scale of the derived consensus error bound (see WIRE_UNIT_ROUNDOFF)."""
    return WIRE_UNIT_ROUNDOFF[wire_dtype_name(wire_dtype)]


def wire_roundtrip(x: jax.Array, wire_dtype) -> jax.Array:
    """Round ``x`` through the wire dtype and decode back to its own dtype —
    the single-program simulation of a compressed exchange (the receiver
    accumulates in fp32 on the decoded values).  STRUCTURAL no-op for f32:
    returns ``x`` itself, so the uncompressed path's graph is untouched."""
    wd = canonical_wire_dtype(wire_dtype)
    if jnp.dtype(wd) == jnp.dtype(x.dtype):
        return x
    return x.astype(wd).astype(x.dtype)


def wire_cast_pair(prec: jax.Array, pm: jax.Array, wire_dtype):
    """Cast the (prec, prec*mu) sufficient-statistic pair to the wire dtype
    for a REAL exchange (collective payload stays compressed on the wire;
    the receiver casts back and accumulates fp32).  Identity for f32 — the
    one shared home of the cast the legacy ``launch.consensus_opt`` helpers
    each duplicated."""
    wd = canonical_wire_dtype(wire_dtype)
    if jnp.dtype(wd) == jnp.dtype(prec.dtype):
        return prec, pm
    return prec.astype(wd), pm.astype(wd)


def softplus(x: jax.Array) -> jax.Array:
    return jax.nn.softplus(x)


# below this y, softplus_inv takes the series branch (see softplus_inv)
_SERIES_MAX = 0.25


def softplus_inv(y: jax.Array) -> jax.Array:
    """Inverse of softplus for y > 0: x s.t. log1p(exp(x)) == y.

    Two branches, both exp/log/polynomial only (see module docstring):

    * y < 0.25: ``log(expm1(y)) = log(y * (expm1(y) / y))`` with the
      Taylor series ``expm1(y) / y = 1 + y/2 + y^2/6 + ... + y^6/5040``
      (truncation error < 2e-9 relative), so tiny y keeps full precision.
      One ``log`` of the product, not ``log(y) + log(series)``: XLA
      rewrites ``log(rsqrt(p))`` to ``-0.5 * log(p)`` in some fusions and
      not others, which would break the bitwise kernel/reference match;
    * y >= 0.25: ``y + log(1 - exp(-y))``; ``1 - exp(-y)`` is at least
      0.22 here, so the subtraction loses < 3e-7 relative, and huge y
      returns exactly y.
    """
    series = 1.0 + y * (1 / 2 + y * (1 / 6 + y * (1 / 24 + y * (
        1 / 120 + y * (1 / 720 + y * (1 / 5040))))))
    small = jnp.log(y * series)
    large = y + jnp.log(1.0 - jnp.exp(-y))
    return jnp.where(y < _SERIES_MAX, small, large)


def softplus_inv_py(y: float) -> float:
    """Pure-Python softplus^-1 (same formulation) for use at trace time /
    under ``jax.eval_shape`` where no jnp ops may run (dry-run path)."""
    return y + math.log(-math.expm1(-y))
