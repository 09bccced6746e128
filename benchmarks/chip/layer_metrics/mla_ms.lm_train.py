"""Device self time per round of the operations whose innermost LM scope
is ``mla`` (latent attention: projections, adapters, RoPE, scores, in the
forward, the rematerialized forward and the backward pass)."""


def reduce(ctx):
    s = ctx.get("lm", {}).get("mla_s")
    return None if s is None else 1e3 * s
