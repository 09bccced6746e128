"""Device self time per round of the operations under the LM's ``moe``
scope: the router, the grouped routed experts (with their sort, gathers and
combine) and the shared experts."""


def reduce(ctx):
    s = ctx.get("lm", {}).get("moe_s")
    return None if s is None else 1e3 * s
