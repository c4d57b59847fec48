"""Pallas TPU kernels for the compute hot-spots of the Flag Aggregator stack.

The paper's per-iteration hot spot is the SVD/Gram of the n x p gradient
matrix (their Sec. 4 complexity note); our Gram-space reformulation reduces
the n-scale work to three memory-bound streaming ops, each implemented as a
Pallas kernel with explicit BlockSpec VMEM tiling:

  gram/          K = G^T G        -- blocked tall-skinny matmul, fp32 VMEM acc
  weighted_sum/  d = c @ G        -- fused weighted combine of worker gradients
  coord_stats/   median/trimmed/  -- odd-even-transposition sort network over
                 meamed/phocas      the (tiny) worker axis, blocked over n
  flash_attn/    online-softmax attention with its own backward (training
                 and prefill of the attention archs on one TPU device)

The aggregation kernels ship ``ops.py`` (public wrapper choosing the
backend from ``impl=``: ``pallas_call`` on TPU, the XLA path elsewhere,
the Pallas interpreter only when asked for with
``impl="pallas_interpret"`` or ``interpret=True``, as CI does); flash
attention's dispatch lives in ``models/attention.py:attend``.  Each ships
``ref.py`` (pure-jnp oracle).
``tests/test_kernels_*.py`` sweep shapes and dtypes asserting allclose
against the oracle.
"""
