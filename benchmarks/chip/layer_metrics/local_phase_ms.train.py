"""Device time of the local phase per round: self time of the operations
under the program's ``local_phase`` scope (sampling, model apply, KL,
gradients and the optimizer of every agent's u steps)."""
from chipbench import layers


def reduce(ctx):
    return layers.layer_ms(ctx, "local_phase")
