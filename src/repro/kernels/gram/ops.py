"""Public wrappers for the Gram kernels.

``gram(G)`` is the per-matrix op (one dispatch per leaf — the *looped*
tree path).  ``tree_gram_fused(leaves)`` is the one-pass tree op, one
chunk plan for the whole pytree: on the Pallas backends the flattened
leaves are packed into a single worker-major (W, N) row-stack feeding
exactly ONE ``pallas_call`` (asserted by jaxpr inspection in
``tests/test_gram_solvers.py``); on XLA the same plan is consumed
piecewise (:func:`ref.tree_gram_pieces_ref` — Gram additivity over static
per-leaf ranges, since a pack copy buys XLA nothing).  Both backends
sample the identical coordinate set (:func:`ref.chunk_schedule`), so
``sketch_stride`` means the same thing everywhere: keep every stride-th
block_n-wide chunk, rescale by the exact inverse sampling fraction.

Callers pick the backend via ``impl=``; ``AggregatorConfig`` defaults to
``xla`` (the dry-run and the tests), and the training launcher passes
``pallas``.

``impl`` convention (shared by every ``kernels/*/ops.py``):

  - ``"xla"``              — the jnp reference (also the test oracle),
  - ``"pallas"``           — the *production* kernel path: ``pallas_call``
    on TPU, the best available XLA lowering elsewhere.  The interpreter is
    never a production path — it re-executes the grid machinery op by op
    and is orders of magnitude off the roofline on CPU,
  - ``"pallas_interpret"`` — force the true Pallas interpreter everywhere
    (how CI exercises the kernel path on CPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.gram.kernel import gram_pallas, tree_gram_pallas
from repro.kernels.gram.ref import gram_ref, tree_gram_pieces_ref


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def gram(G, *, impl: str = "xla", block_n: int = 1024):
    """K = G^T G (fp32). impl: 'xla' | 'pallas' | 'pallas_interpret'."""
    if impl == "xla":
        return gram_ref(G)
    if impl == "pallas":
        if on_tpu():
            return gram_pallas(G, block_n=block_n, interpret=False)
        return gram_ref(G)              # production fallback off-TPU
    if impl == "pallas_interpret":
        return gram_pallas(G, block_n=block_n, interpret=True)
    raise ValueError(f"unknown impl {impl!r}")


def pack_leaves(leaves, *, gram_dtype: str = "float32") -> jnp.ndarray:
    """(W, ...) leaves -> one worker-major (W, N) row-stack.

    ``gram_dtype`` != 'float32' down-casts the stack before the matmul
    (bf16-in / fp32-accumulate); otherwise leaves keep their own dtype
    (promoted to a common one only if they disagree).
    """
    if not leaves:
        raise ValueError("pack_leaves: empty leaf list")
    target = (jnp.dtype(gram_dtype) if gram_dtype != "float32"
              else jnp.result_type(*leaves))
    W = leaves[0].shape[0]
    return jnp.concatenate(
        [leaf.reshape(W, -1).astype(target) for leaf in leaves], axis=1)


def tree_gram_fused(leaves, *, sketch_stride: int = 1,
                    gram_dtype: str = "float32", impl: str = "xla",
                    block_n: int = 1024) -> jnp.ndarray:
    """One-pass (W, W) fp32 Gram of a whole leaf list — one kernel call.

    Args:
      leaves: worker-major arrays, every leaf shaped ``(W, ...)``.
      sketch_stride: keep every stride-th block_n-wide chunk of the packed
        stack (folded into the kernel index map — no strided copy), with
        the exact inverse-fraction rescale so the diagonal stays unbiased.
      gram_dtype: dtype the packed stack is cast to *before* the
        contraction (accumulation stays fp32).
      impl: 'xla' | 'pallas' | 'pallas_interpret'.
    """
    if impl == "xla" or (impl == "pallas" and not on_tpu()):
        # XLA consumes the identical chunk plan piecewise (Gram
        # additivity) — packing here would only add a (W, n) copy that
        # the dot cannot amortize on CPU; the dispatch-count win the pack
        # buys is a Pallas-only concern.
        if gram_dtype != "float32":
            target = jnp.dtype(gram_dtype)
            leaves = [leaf.astype(target) for leaf in leaves]
        return tree_gram_pieces_ref(leaves, sketch_stride=sketch_stride,
                                    block_n=block_n)
    with jax.named_scope("pack"):
        X = pack_leaves(leaves, gram_dtype=gram_dtype)
    if impl == "pallas":
        return tree_gram_pallas(X, sketch_stride=sketch_stride,
                                block_n=block_n, interpret=False)
    if impl == "pallas_interpret":
        return tree_gram_pallas(X, sketch_stride=sketch_stride,
                                block_n=block_n, interpret=True)
    raise ValueError(f"unknown impl {impl!r}")
