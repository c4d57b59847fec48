"""Public wrapper for the weighted-combine kernel (see gram/ops.py)."""

from __future__ import annotations

from repro.kernels.gram.ops import on_tpu
from repro.kernels.weighted_sum.kernel import weighted_sum_pallas
from repro.kernels.weighted_sum.ref import weighted_sum_ref


def weighted_sum(G, c, *, impl: str = "xla", block_n: int = 2048):
    """d = c @ G for worker-major G (W, n), c (W,).
    impl: 'xla' | 'pallas' | 'pallas_interpret'."""
    if impl == "xla":
        return weighted_sum_ref(G, c)
    if impl == "pallas":
        if on_tpu():
            return weighted_sum_pallas(G, c, block_n=block_n,
                                       interpret=False)
        return weighted_sum_ref(G, c)   # production fallback off-TPU
    if impl == "pallas_interpret":
        return weighted_sum_pallas(G, c, block_n=block_n, interpret=True)
    raise ValueError(f"unknown impl {impl!r}")
