"""Host time to build one gossip window, per window of the traced run:
the program's ``session.w_build`` span (the clock's window draw and
conserve-rule weights) plus its ``gossip.window_build`` span (fault draws
and the window's host-to-device arrays)."""

SPANS = ("session.w_build", "gossip.window_build")


def reduce(ctx):
    first = ctx["traffic"]["check_rounds"]
    spans = [s for s in ctx["spans"]
             if s.name in SPANS and s.attrs.get("round", -1) >= first]
    rounds = {s.attrs["round"] for s in spans}
    if not rounds:
        return None
    return 1e-3 * sum(s.dur_us for s in spans) / len(rounds)
