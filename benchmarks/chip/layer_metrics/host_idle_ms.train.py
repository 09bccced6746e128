"""Device idle time inside the program's ``session.round`` span per round:
chip 0 idle while the host is inside a round (window build, batches,
dispatch, the wait for the losses); nothing when the trace holds no
``session.round`` annotation."""
from chipbench import layers


def reduce(ctx):
    lay = ctx.get("layers")
    return None if lay is None else layers.per_round_ms(ctx, lay.round_idle_s)
