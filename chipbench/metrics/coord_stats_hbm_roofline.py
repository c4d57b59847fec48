"""The selection kernel's share of its HBM roofline: the coordinate-wise
median of W rows reads W n and writes n fp32 per step (a W-wide
compare-exchange network is a few operations a byte, far under the
chip's FLOP bound), split over the devices that share the coordinates,
at the peak HBM bandwidth."""

from chipbench import counts
from chipbench.metrics import coord_stats_ms


def read(ctx):
    ms = coord_stats_ms.read(ctx)
    if ms is None:
        return None
    least = counts.combine_bytes(ctx.config, ctx.traffic["workers"],
                                 ctx.coord_devices) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ms * 1e-3)
