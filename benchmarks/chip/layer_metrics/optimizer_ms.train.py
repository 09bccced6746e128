"""Device time of the optimizer per round: self time of the operations
under ``optimizer`` inside the program's local phase (the Adam update and
its application to the posterior)."""
from chipbench import layers


def reduce(ctx):
    lay = ctx.get("layers")
    return None if lay is None else layers.per_round_ms(
        ctx, lay.part_s.get("optimizer"))
