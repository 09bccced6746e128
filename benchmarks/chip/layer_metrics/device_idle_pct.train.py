"""Share of the traced window in which no operation ran on the device
(training cells): 1 - union of device-op intervals / window."""


def reduce(ctx):
    return ctx["reduced"].idle_pct
