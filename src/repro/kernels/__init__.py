# Pallas TPU kernels for the paper's compute hot-spots:
#   consensus        fused precision-weighted posterior consensus (eq. 6)
#   gauss_vi         fused Bayes-by-Backprop sample + KL (eq. 5)
#   flash_attention  blocked causal/SWA attention, forward only (no caller
#                    in the model)
#   causal_flash     MLA's causal attention on the TPU: JAX's splash
#                    kernels (forward, dq, dkv), tested against
#                    models.attention.chunked_attention
# The first three have pure-jnp oracles in ref.py (consensus and
# gauss_vi jit'd wrappers in ops.py too); all are validated in
# interpret=True mode on CPU and compiled via Mosaic on TPU.
from repro.kernels import ops, ref
from repro.kernels.consensus import (
    consensus_fused,
    consensus_fused_network,
    consensus_fused_sparse,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gauss_vi import sample_and_kl_fused

__all__ = [
    "ops",
    "ref",
    "consensus_fused",
    "consensus_fused_network",
    "consensus_fused_sparse",
    "flash_attention",
    "sample_and_kl_fused",
]
