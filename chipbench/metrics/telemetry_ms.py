"""Device time per step of the in-step telemetry (scope ``telemetry``:
per-worker norms, influence, global norm, metric means)."""

from chipbench import scopes


def read(ctx):
    return scopes.ms(ctx, stage="telemetry")
