"""Blocked tall-skinny Gram kernels:  K = G^T G,  G in R^{n x p},  p << n.

Two kernels live here:

* :func:`gram_pallas` — the original per-matrix kernel (one ``pallas_call``
  per (n, p) matrix; the *looped* tree path dispatches it once per leaf).
* :func:`tree_gram_pallas` — the fused one-pass tree kernel: the whole
  worker-major gradient row-stack (every leaf concatenated, (W, N)) streams
  through a single ``pallas_call`` as fixed-size (W, block_n) chunks
  into one fp32 accumulator.  ``sketch_stride`` is folded into the index
  map (grid step j reads the chunk at block index j*stride) so the sketch
  never materializes a strided+scaled copy; the wrapper rescales once by
  the exact sampling fraction from :func:`ref.chunk_schedule`.

TPU mapping (both).  Tiles stream HBM -> VMEM; the fp32 accumulator lives
in the *output* VMEM block, which every grid step revisits (index_map is
constant) — the canonical Pallas reduction pattern.  :func:`gram_pallas`
pads its worker (lane) axis to 128 once per call.  :func:`tree_gram_pallas`
reads the (W, N) stack in place: its blocks are (W, block_n) with the
worker axis as the full block dim, so nothing is padded or copied in HBM
(a 128-row pad of a whole-model stack would be 128x the gradient bytes);
the ragged last chunk is zero-masked in VMEM.  Contractions are issued with
preferred_element_type=float32 so bf16 gradients accumulate in fp32 (bf16
Gram accumulation is one of the §Perf experiments — see ops.gram(precision=...)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.gram.ref import chunk_schedule


def _gram_kernel(g_ref, k_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        k_ref[...] = jnp.zeros_like(k_ref)

    g = g_ref[...]                                   # (block_n, p_pad)
    k_ref[...] += jax.lax.dot_general(
        g, g,
        dimension_numbers=(((0,), (0,)), ((), ())),  # contract over n-block
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gram_pallas(G: jnp.ndarray, *, block_n: int = 1024,
                interpret: bool = False) -> jnp.ndarray:
    """K = G^T G via pallas_call.  G: (n, p); returns (p, p) fp32.

    The wrapper pads n up to a block multiple and p up to the 128-lane
    width; padding rows/cols are zero so they do not perturb K.
    """
    n, p = G.shape
    p_pad = max(128, -(-p // 128) * 128)
    n_pad = -(-n // block_n) * block_n
    Gp = jnp.zeros((n_pad, p_pad), G.dtype).at[:n, :p].set(G)

    K = pl.pallas_call(
        _gram_kernel,
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec((block_n, p_pad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((p_pad, p_pad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((p_pad, p_pad), jnp.float32),
        interpret=interpret,
    )(Gp)
    return K[:p, :p]


def _make_tree_gram_kernel(n: int, block_n: int, stride: int):
    ragged = n % block_n != 0

    def kernel(x_ref, k_ref):
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _init():
            k_ref[...] = jnp.zeros_like(k_ref)

        x = x_ref[...]                               # (W, block_n)
        if ragged:
            # the last chunk runs past n: its tail lanes hold whatever the
            # DMA left there, so zero them before they reach the MXU.
            col = (j * stride * block_n
                   + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
            x = jnp.where(col < n, x, jnp.zeros_like(x))
        k_ref[...] += jax.lax.dot_general(
            x, x,
            dimension_numbers=(((1,), (1,)), ((), ())),  # contract n-chunk
            precision=jax.lax.Precision.HIGHEST,         # fp32 on the MXU
            preferred_element_type=jnp.float32,
        )
    return kernel


@functools.partial(jax.jit, static_argnames=("sketch_stride", "block_n",
                                             "interpret"))
def tree_gram_pallas(X: jnp.ndarray, *, sketch_stride: int = 1,
                     block_n: int = 1024,
                     interpret: bool = False) -> jnp.ndarray:
    """One-pass fused Gram:  K = scale * X_S X_S^T in a single pallas_call.

    X: (W, N) worker-major row-stack of every flattened gradient leaf
    (bf16 or fp32), read in place.  X_S is the chunk subset of
    :func:`ref.chunk_schedule` — with ``sketch_stride`` > 1 the grid visits
    every stride-th (W, block_n) chunk via the index map, skipping the rest
    of HBM entirely.  Returns (W, W) fp32.
    """
    w, n = X.shape
    kept, _, scale = chunk_schedule(n, block_n, sketch_stride)
    bn = n if n <= block_n else block_n          # one chunk: the full row
    stride = max(1, sketch_stride)
    K = pl.pallas_call(
        _make_tree_gram_kernel(n, bn, stride),
        grid=(kept,),
        in_specs=[pl.BlockSpec((w, bn), lambda j: (0, j * stride))],
        out_specs=pl.BlockSpec((w, w), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((w, w), jnp.float32),
        interpret=interpret,
        name="tree_gram",
    )(X)
    return K * scale if scale != 1.0 else K
