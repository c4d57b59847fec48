"""The combine kernel's share of its HBM roofline: W n reads and n
writes of fp32 per step, split over the devices that share the
coordinates, at the peak HBM bandwidth."""

from chipbench import counts
from chipbench.metrics import combine_ms


def read(ctx):
    ms = combine_ms.read(ctx)
    if ms is None:
        return None
    least = counts.combine_bytes(ctx.config, ctx.traffic["workers"],
                                 ctx.coord_devices) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ms * 1e-3)
