"""Fused weighted-combine kernel:  d = c @ G  (the FA update, Alg. 1 line 6).

This is a memory-bound streaming op (read W*n, write n): each grid step
pulls a worker-major (W, block_n) tile of G into VMEM — the worker axis is
the full (unpadded) block dim, so the gradient stack is read in place with
no transpose and no padding copy — multiplies by the replicated (W, 1)
weight column c (VMEM-resident, index_map constant), reduces over the
worker (sublane) axis and writes a lane-dense (1, block_n) output tile.
The last tile may run past n: Pallas drops its out-of-bounds writes, and
the garbage it reads there only reaches those dropped lanes.  Fusing the
scale-and-reduce avoids materializing the scaled G and keeps arithmetic
intensity at the streaming roofline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wsum_kernel(g_ref, c_ref, d_ref):
    g = g_ref[...].astype(jnp.float32)        # (W, block_n)
    c = c_ref[...].astype(jnp.float32)        # (W, 1)
    d_ref[...] = jnp.sum(g * c, axis=0, keepdims=True).astype(d_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def weighted_sum_pallas(G: jnp.ndarray, c: jnp.ndarray, *,
                        block_n: int = 2048, interpret: bool = False):
    """d = c @ G.  G: worker-major (W, n), c: (W,) -> (n,) in G.dtype."""
    w, n = G.shape
    bn = n if n <= block_n else block_n
    d = pl.pallas_call(
        _wsum_kernel,
        grid=(pl.cdiv(n, bn),),
        in_specs=[pl.BlockSpec((w, bn), lambda i: (0, i)),
                  pl.BlockSpec((w, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), G.dtype),
        interpret=interpret,
        name="weighted_sum",
    )(G, c.astype(jnp.float32).reshape(w, 1))
    return d[0]
