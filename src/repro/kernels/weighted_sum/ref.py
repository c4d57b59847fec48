"""Pure-jnp oracle for the weighted-combine kernel."""

from __future__ import annotations

import jax.numpy as jnp


def weighted_sum_ref(G: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """d = c @ G with fp32 accumulation.  G: worker-major (W, n), c: (W,)
    -> d: (n,) in G.dtype (the gradient dtype the optimizer consumes)."""
    d = c.astype(jnp.float32) @ G.astype(jnp.float32)
    return d.astype(G.dtype)
