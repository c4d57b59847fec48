"""Device time of the Pallas coordinate-wise selection kernel (ops
``coord_stats_pallas``, named after its jitted entry point) per step and
device."""

PATTERN = r"\bcoord_stats_pallas\b"


def read(ctx):
    return ctx.per_step_ms(PATTERN)
