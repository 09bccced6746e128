# Pallas TPU kernels for the paper's compute hot-spots:
#   consensus        fused precision-weighted posterior consensus (eq. 6)
#   gauss_vi         fused Bayes-by-Backprop sample + KL (eq. 5)
#   flash_attention  blocked causal/SWA attention, forward only (no caller
#                    in the model)
#   causal_flash     MLA's causal attention on the TPU: JAX's splash
#                    kernels (forward, dq, dkv), tested against
#                    models.attention.chunked_attention
#   grouped_matmul   the routed experts' grouped products on the TPU:
#                    JAX's megablox kernel, tested against
#                    jax.lax.ragged_dot
# The consensus kernels are checked against core.flat's XLA references,
# gauss_vi and flash_attention against the pure-jnp oracles in ref.py
# (gauss_vi's pytree wrapper is ops.sample_and_kl); all are validated in
# interpret=True mode on CPU and compiled via Mosaic on TPU.
from repro.kernels import ops, ref
from repro.kernels.consensus import consensus_fused_network
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gauss_vi import sample_and_kl_fused

__all__ = [
    "ops",
    "ref",
    "consensus_fused_network",
    "flash_attention",
    "sample_and_kl_fused",
]
