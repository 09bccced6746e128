"""Device time of the Pallas consensus and payload-validity kernels per
round (self time of their custom-call events in the trace); nothing when
no such kernel ran."""

KERNELS = ("consensus_fused", "payload_validity")


def reduce(ctx):
    s = ctx["reduced"].kernel_s(KERNELS)
    return None if s is None else 1e3 * s / ctx["window"]["rounds"]
