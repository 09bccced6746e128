"""The largest routed expert's token count over the mean count, in the
worst MoE layer, over the traced window's rounds (the registry gauge
``model.expert_load_max``, from the router's counts)."""


def reduce(ctx):
    return ctx.get("lm", {}).get("expert_load_max")
