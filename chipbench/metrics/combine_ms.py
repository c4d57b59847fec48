"""Device time of the Pallas combine kernel (ops ``weighted_sum``) per
step and device."""

PATTERN = r"\bweighted_sum\b"


def read(ctx):
    return ctx.per_step_ms(PATTERN)
