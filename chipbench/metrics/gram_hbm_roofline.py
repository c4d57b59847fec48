"""The Gram kernel's share of its HBM roofline.  At these W the Gram is
bound by bandwidth, not FLOPs (2 W^2 n FLOPs against 4 W n bytes: W/2
FLOP per byte, far under the chip's 240): the least time is the fp32
(W, n) stack read once, split over the devices that share it, at the
peak HBM bandwidth."""

from chipbench import counts
from chipbench.metrics import gram_ms


def read(ctx):
    ms = gram_ms.read(ctx)
    if ms is None:
        return None
    least = counts.gram_bytes(ctx.config, ctx.traffic["workers"],
                              ctx.coord_devices) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ms * 1e-3)
