"""Device time of eq. (6) per round: self time of the operations under the
program's ``consensus`` scope, kernels and XLA glue alike (the Pallas
kernels, the validity probe nested in the quarantined path, the segment
sum and its gathers)."""
from chipbench import layers


def reduce(ctx):
    return layers.layer_ms(ctx, "consensus")
