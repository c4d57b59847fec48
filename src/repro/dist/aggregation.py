"""Worker-major pytree aggregation — the distributed form of every rule.

The distributed runtime holds gradients as a *pytree* whose leaves carry a
leading worker axis ``(W, ...)`` (the output of ``vmap(grad)``).  The naive
way to aggregate is to flatten everything into the ``(W, n)`` matrix the
single-host reference code consumes — but at n ~ 1e9 that materialization
is exactly the parameter-server bottleneck the Gram-space derivation in
:mod:`repro.core.gram` removes.  This module therefore never builds the
flat stack.  Instead it exploits two structural facts:

* **Gram additivity** — ``K = G G^T = sum_leaf  G_leaf G_leaf^T``: the
  (W, W) Gram matrix is one tall-skinny contraction over the packed leaf
  stream (``tree_gram``): the fused one-pass kernel in
  ``repro.kernels.gram`` issues a *single* ``pallas_call`` for the whole
  pytree (Pallas on TPU, XLA elsewhere; a per-shard psum on a real mesh),
  with the legacy per-leaf loop kept behind ``fused=False`` for the
  tests.
* **Combine linearity** — any rule whose output is a fixed linear
  combination ``d = G^T c`` of worker gradients applies leafwise
  (``tree_combine``), a weighted reduction over the worker axis.

That covers FA itself (weights from ``fa_weights_from_gram``), PCA-top-m,
mean, geometric median (Weiszfeld runs in weight space: every iterate stays
in the gradient span, so distances are Gram-computable), and the
Krum-family selections (scores need only pairwise distances).  The
remaining baselines are coordinate-wise (median / trimmed mean / MeaMed /
Phocas), which commute with the pytree split and apply per leaf; Bulyan is
the hybrid — Gram-space selection via ``bulyan_select``, then the
coordinate-wise trimmed mean per leaf over the selected workers.  Every
path is *exactly* the flat reference (asserted at 2e-3 in
``tests/test_dist.py`` and generatively in ``tests/test_properties.py``).

``sketch_stride`` subsamples the gradient stream when forming the Gram
matrix (every stride-th chunk on the fused path, folded into the kernel
index map; rescaled so the diagonal stays unbiased) — an O(stride) cut in
Gram FLOPs/bytes used by the production configs; the combine always uses
the full gradients.

:func:`compressed_aggregate` is the worker->server compressed entry point:
it routes a ``repro.comm`` codec around ``aggregate_tree`` — sketch codecs
feed the Gram path directly (weights from compressed payloads, exact
combine), everything else goes through EF-compensated encode/decode.

Both entry points take ``sharded=`` to run mesh-native
(:mod:`repro.dist.sharded`): coordinate shards spread over the devices,
partial Grams meet in one ``(W, W)`` psum, the combine and the
coordinate-wise rules stay shard-local — no device ever holds the full
stack.  See docs/sharded_aggregation.md.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.analysis.contract import contract
from repro.comm.compressors import CommConfig, dense_bits, get_codec
from repro.comm.error_feedback import ef_encode_decode
from repro.core import aggregators
from repro.core.flag import FlagConfig
from repro.core.gram import fa_weights_from_gram
from repro.kernels.coord_stats.ops import (bulyan_select as bulyan_select_op,
                                           coord_stat,
                                           krum_scores as krum_scores_op)
from repro.kernels.gram.ops import gram as gram_kernel, tree_gram_fused
from repro.kernels.weighted_sum.ops import weighted_sum as weighted_sum_kernel

__all__ = ["AggregatorConfig", "tree_gram", "tree_combine", "aggregate_tree",
           "compressed_aggregate", "GRAM_RULES", "COORDWISE_RULES"]


@dataclass(frozen=True)
class AggregatorConfig:
    """Which rule the distributed step runs, and how the Gram is formed.

    ``f`` is the assumed Byzantine count (Krum family / trimming width);
    ``flag`` carries the FA hyper-parameters; ``sketch_stride`` > 1 sketches
    the Gram matrix (see module docstring); ``gram_dtype`` down-casts the
    leaf matrices before the Gram matmul (accumulation stays fp32);
    ``impl`` picks the kernel backend ('xla' | 'pallas' | 'pallas_interpret').
    """

    name: str = "flag"
    f: int = 1
    flag: FlagConfig = FlagConfig()
    sketch_stride: int = 1
    gram_dtype: str = "float32"
    impl: str = "xla"


def _leaf_matrix(leaf: jnp.ndarray, stride: int, dtype: str):
    """(W, ...) leaf -> ((W, n_kept) matrix, fp32 Gram rescale).

    Deterministic stride-subsample with the *exact* inverse kept fraction
    as the rescale (``n / n_kept`` — unbiased diagonal even when the leaf
    width is not a multiple of the stride).  The scale is returned
    separately and applied to the fp32 Gram accumulator, never to the
    matrix itself: folding it into a bf16 ``gram_dtype`` matrix would
    truncate the scale to bf16 before the contraction.  Leaves narrower
    than the stride keep every coordinate (scale 1, exact) instead of
    keeping one sample and inflating it ``stride``-fold.
    """
    M = leaf.reshape(leaf.shape[0], -1)
    scale = 1.0
    if stride > 1 and M.shape[1] > stride:
        n = M.shape[1]
        M = M[:, ::stride]
        scale = n / M.shape[1]
    if dtype != "float32":
        M = M.astype(jnp.dtype(dtype))
    return M, scale


def tree_gram(tree, sketch_stride: int = 1, *, gram_dtype: str = "float32",
              impl: str = "xla", fused: bool = True) -> jnp.ndarray:
    """(W, W) Gram matrix of the flattened worker gradients, one pass.

    Equals ``flat @ flat.T`` for the concatenated ``(W, n)`` matrix.
    The default *fused* path packs every leaf into a single worker-major
    chunk stream and issues exactly one kernel call for the whole pytree
    (one ``pallas_call`` on the Pallas backends; see
    ``repro.kernels.gram.ops.tree_gram_fused``), with ``sketch_stride``
    folded into the kernel index map — every stride-th block_n-wide chunk
    is read, the rest of HBM is skipped, and the result is rescaled by the
    exact inverse sampling fraction (diagonal-unbiased; weights only — the
    combine stays exact).  ``fused=False`` keeps the per-leaf loop (one
    dispatch + re-pad per leaf, element-stride sketching) as the
    reference path the tests compare against.

    Args:
      tree: worker-major pytree, every leaf shaped ``(W, ...)``.
      sketch_stride: fused path — keep every stride-th chunk of the packed
        stack; looped path — keep every stride-th coordinate of each leaf
        (leaves narrower than the stride stay exact), with the exact
        inverse kept fraction applied to the fp32 Gram.  Both keep the
        diagonal unbiased.
      gram_dtype: dtype the gradient stack is cast to *before* the matmul
        (accumulation stays fp32).
      impl: kernel backend — ``'xla'`` | ``'pallas'`` | ``'pallas_interpret'``.
      fused: one-pass fused kernel (default) vs per-leaf loop.
    Returns:
      ``(W, W)`` fp32 Gram matrix ``K`` with ``K[i, j] = <g_i, g_j>``.
    """
    leaves = jax.tree.leaves(tree)
    if not leaves:
        raise ValueError("tree_gram: empty gradient pytree")
    with jax.named_scope("gram"):
        if fused:
            return tree_gram_fused(leaves, sketch_stride=sketch_stride,
                                   gram_dtype=gram_dtype, impl=impl)
        W = leaves[0].shape[0]
        K = jnp.zeros((W, W), jnp.float32)
        for leaf in leaves:
            M, scale = _leaf_matrix(leaf, sketch_stride, gram_dtype)
            # kernels.gram computes G^T G for column-major (n, p) input in
            # fp32; the sketch rescale is applied to the fp32 result
            # (post-cast).
            K = K + gram_kernel(M.T, impl=impl) * scale
        return K


def tree_combine(tree, c: jnp.ndarray, *, impl: str = "xla"):
    """Weighted worker combine ``d = sum_w c_w g_w`` applied per leaf.

    The pytree analogue of ``flat.T @ c`` — the only n-dependent work of
    every linear-combination rule (a weighted all-reduce on a real mesh).

    Args:
      tree: worker-major pytree, every leaf shaped ``(W, ...)``.
      c: ``(W,)`` combination weights (cast to each leaf's dtype).
      impl: kernel backend — ``'xla'`` | ``'pallas'`` | ``'pallas_interpret'``.
    Returns:
      Pytree with the worker axis reduced away (leaf shapes ``(...)``).
    """
    def one(leaf):
        if impl != "xla":
            # the kernel reads the worker-major (W, n) view in place and
            # upcasts both operands to fp32 in VMEM, so c keeps full
            # precision end to end; only the output is leaf-dtype.
            d = weighted_sum_kernel(
                leaf.reshape(leaf.shape[0], -1),
                c.astype(jnp.float32), impl=impl)
            return d.reshape(leaf.shape[1:])
        # contract in fp32 (c stays fp32, bf16 leaves accumulate in fp32
        # via preferred_element_type) and cast only the result — casting c
        # to bf16 first would truncate the combine weights before the
        # reduction.
        d = jax.lax.dot_general(
            c.astype(jnp.float32), leaf,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return d.astype(leaf.dtype)
    with jax.named_scope("combine"):
        return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# Gram-space combination weights per rule
# ---------------------------------------------------------------------------

def _geomed_weights(K: jnp.ndarray, n_iter: int = 8, eps: float = 1e-8,
                    mask: jnp.ndarray | None = None) -> jnp.ndarray:
    """Weiszfeld in weight space: z = G^T w stays in span(G), so
    ||g_i - z||^2 = K_ii - 2 (K w)_i + w^T K w.  Iterates identically to
    ``aggregators.geometric_median`` (init w = 1/p == init z = mean).
    With ``mask`` the weight support stays on active workers — every
    iterate is then the Weiszfeld step of the active submatrix.

    Degenerate memberships are exact by construction, not by luck: with a
    single active worker ``r`` has one nonzero entry, so the normalized
    iterate is that worker's exact one-hot (``r_i / r_i == 1.0`` in IEEE,
    independent of the ``eps`` distance clip); with zero active workers
    ``r`` is all-zero and the ``where`` keeps the previous (all-zero)
    iterate instead of dividing by the ``1e-30`` clamp — no NaN/Inf
    either way, even at ``eps = 0`` (regression-tested in
    ``tests/test_membership.py``)."""
    p = K.shape[0]
    eps = max(eps, 1e-30)                 # rsqrt(clip(., 0)) would be inf
    m = jnp.ones((p,), K.dtype) if mask is None else mask.astype(K.dtype)
    w0 = m / jnp.maximum(jnp.sum(m), 1.0)

    def body(w, _):
        Kw = K @ w
        d2 = jnp.diag(K) - 2.0 * Kw + w @ Kw
        r = jax.lax.rsqrt(jnp.clip(d2, eps)) * m
        s = jnp.sum(r)
        # s == 0 iff no active worker carries reweighting mass: w is
        # already the (all-zero) answer — keep it.
        return jnp.where(s > 0.0, r / jnp.maximum(s, 1e-30), w), None

    w, _ = jax.lax.scan(body, w0, None, length=n_iter)
    return w


def _selection_weights(K: jnp.ndarray, name: str, f: int,
                       impl: str = "xla") -> jnp.ndarray:
    """Krum-family combination weights from the Gram matrix."""
    p = K.shape[0]
    D2 = aggregators.sq_dists_from_gram(K)
    s = krum_scores_op(D2, f=f, impl=impl)
    if name == "krum":
        return jax.nn.one_hot(jnp.argmin(s), p, dtype=K.dtype)
    q = max(p - f - 2, 1)
    _, idx = jax.lax.top_k(-s, q)
    return jnp.zeros((p,), K.dtype).at[idx].add(1.0 / q)


def _gram_weights(K: jnp.ndarray, cfg: AggregatorConfig,
                  mask: jnp.ndarray | None = None):
    """(c, aux) for every rule expressible as a fixed combine d = G^T c.

    ``mask`` restricts every rule to the active worker subset (masked Gram
    rows — see repro.dist.membership); c is zero at inactive workers.
    The (W, W) solves are tiny, so their matmuls run in fp32 on every
    backend: the TPU's default precision would round fp32 operands to
    bf16, and the weights decide which workers the update trusts.
    """
    p = K.shape[0]
    with jax.named_scope("solve"), jax.default_matmul_precision("highest"):
        if cfg.name == "flag":
            return fa_weights_from_gram(K, cfg.flag, mask=mask)
        if cfg.name == "pca":
            pca_cfg = FlagConfig(m=cfg.flag.m, lam=0.0, regularizer="none",
                                 n_iter=1)
            return fa_weights_from_gram(K, pca_cfg, mask=mask)
        if cfg.name == "mean":
            if mask is None:
                return jnp.full((p,), 1.0 / p, K.dtype), {}
            m = mask.astype(K.dtype)
            return m / jnp.maximum(jnp.sum(m), 1.0), {}
        if cfg.name == "geomed":
            return _geomed_weights(K, mask=mask), {}
        if cfg.name in ("krum", "multi_krum"):
            if mask is None:
                return _selection_weights(K, cfg.name, cfg.f, cfg.impl), {}
            return aggregators.masked_selection_weights(
                aggregators.sq_dists_from_gram(K), cfg.name, cfg.f, mask), {}
    raise KeyError(cfg.name)


GRAM_RULES = frozenset({"flag", "pca", "mean", "geomed", "krum",
                        "multi_krum"})
COORDWISE_RULES = frozenset({"median", "trimmed_mean", "meamed", "phocas"})


@contract(fp32_contractions=True, no_host_transfers=True, mask_traced=True,
          no_full_width=True, kernel_race=True, kernel_budget=True)
def aggregate_tree(tree, cfg: AggregatorConfig, *, gram=None, mask=None,
                   sharded=None):
    """Aggregate a worker-major gradient pytree.

    Carries the graph contract (checked under ``REPRO_CONTRACTS=1`` /
    :func:`repro.analysis.enable_contracts`, free otherwise): fp32
    accumulation for every low-precision contraction, no host transfers
    in the graph, the membership mask consumed as a traced operand, and —
    with ``sharded=`` — no per-device tensor holding a full coordinate
    width.

    Args:
      tree: worker-major gradient pytree, every leaf shaped ``(W, ...)``.
      cfg: which rule runs and how the Gram matrix is formed.
      gram: optional precomputed ``(W, W)`` Gram estimate.  When given, the
        Gram-space rules (and Bulyan's selection) skip ``tree_gram`` and
        run their weight computation on it instead — this is how sketch
        codecs (``repro.comm``) feed FA with compressed payloads: weights
        come from the sketch Gram, the combine still uses the exact local
        gradients.  Coordinate-wise rules have no Gram stage, so passing
        ``gram`` for them is an error rather than a silent no-op.
      mask: optional (W,) active-worker membership (bool or 0/1 float, a
        *traced* value — see :mod:`repro.dist.membership`).  Every rule
        then operates on the active subset only: masked Gram rows for the
        FA/Krum family, masked leaves with dynamic order statistics for
        the coordinate rules.  Shapes are unchanged, so membership changes
        never recompile; inactive workers get combine weight exactly 0.
      sharded: mesh-shard the aggregation (:mod:`repro.dist.sharded`):
        the coordinate axis of every leaf spreads over the mesh devices,
        each device computes the partial Gram of its shard, the ``(W, W)``
        Gram meets in one ``psum``, weights run replicated, and the
        combine / coordinate rules stay shard-local — the full ``(W, n)``
        stack never exists on any device.  Pass a ``jax.sharding.Mesh``,
        or ``True`` to use the active :func:`repro.dist.sharding.
        use_sharding` mesh.  Composes with ``gram=`` (the override skips
        the psum stage) and ``mask=``.  ``None``/``False`` keeps the
        single-device path.
    Returns:
      ``(d_tree, aux)`` — ``d_tree`` has the worker axis reduced away (same
      treedef, leaf shapes ``(...)``); ``aux['weights']`` always holds a
      ``(W,)`` per-worker combination-weight vector (uniform for
      coordinate-wise rules, where no single linear combine exists) — the
      ``fa_weights`` training metric.
    """
    leaves = jax.tree.leaves(tree)
    if not leaves:
        raise ValueError("aggregate_tree: empty gradient pytree")
    W = leaves[0].shape[0]
    if gram is not None and cfg.name in COORDWISE_RULES:
        raise ValueError(f"aggregator {cfg.name!r} is coordinate-wise and "
                         "cannot consume a precomputed Gram matrix")
    if mask is not None:
        mask = jnp.asarray(mask).astype(jnp.float32)

    if sharded:                       # Mesh instances are always truthy
        from jax.sharding import Mesh
        from repro.dist.sharded import sharded_aggregate_tree
        if isinstance(sharded, Mesh):
            mesh = sharded
        else:
            from repro.dist.sharding import current_mesh
            mesh = current_mesh()
            if mesh is None:
                raise ValueError(
                    "aggregate_tree(sharded=True) needs an active mesh: "
                    "wrap the call in repro.dist.sharding.use_sharding(...)"
                    " or pass sharded=<jax.sharding.Mesh>")
        return sharded_aggregate_tree(tree, cfg, mesh=mesh, gram=gram,
                                      mask=mask)

    if cfg.name in GRAM_RULES:
        K = gram if gram is not None else tree_gram(
            tree, cfg.sketch_stride, gram_dtype=cfg.gram_dtype,
            impl=cfg.impl)
        c, aux = _gram_weights(K, cfg, mask)
        d = tree_combine(tree, c, impl=cfg.impl)
        return d, {**aux, "weights": c}

    if cfg.name in COORDWISE_RULES:
        # Coordinate-wise rules commute with the pytree split: leafwise
        # application == the flat reference on the concatenated matrix.
        # coord_stat routes cfg.impl — the streaming Pallas selection
        # network or the jnp references — with identical (masked)
        # semantics either way.
        with jax.named_scope("coord_stats"):
            d = jax.tree.map(
                lambda g: coord_stat(g.reshape(W, -1), op=cfg.name, f=cfg.f,
                                     impl=cfg.impl, mask=mask
                                     ).reshape(g.shape[1:]),
                tree)
        if mask is None:
            return d, {"weights": jnp.full((W,), 1.0 / W, jnp.float32)}
        wa = jnp.maximum(jnp.sum(mask), 1.0)
        return d, {"weights": mask / wa}

    if cfg.name == "bulyan":
        # Selection is distance-only -> Gram space; the final trimmed mean
        # over the theta selected workers is coordinate-wise -> per leaf.
        K = gram if gram is not None else tree_gram(
            tree, cfg.sketch_stride, gram_dtype=cfg.gram_dtype,
            impl=cfg.impl)
        if mask is None:
            with jax.named_scope("solve"):
                D2 = aggregators.sq_dists_from_gram(K)
                picks = bulyan_select_op(D2, f=cfg.f, impl=cfg.impl)
            theta = picks.shape[0]
            # Bulyan's coordinate stage IS MeaMed with f' = 2f on the
            # selected stack: mean of max(theta - 2f, 1) values closest to
            # the median — so the same streaming kernel serves both.
            def one(g):
                S = g.reshape(W, -1)[picks]
                return coord_stat(S, op="meamed", f=2 * cfg.f,
                                  impl=cfg.impl).reshape(g.shape[1:])

            with jax.named_scope("coord_stats"):
                d = jax.tree.map(one, tree)
            with jax.named_scope("solve"):
                c = jnp.zeros((W,), jnp.float32).at[picks].add(1.0 / theta)
            return d, {"weights": c}

        with jax.named_scope("solve"):
            D2 = aggregators.sq_dists_from_gram(K)
            selected, theta = aggregators.masked_bulyan_select(D2, cfg.f,
                                                               mask)
            sel_f = selected.astype(jnp.float32)

        def one_masked(g):
            # masked MeaMed over the selected workers: W_a = theta, so the
            # keep-count max(W_a - 2f, 1) equals Bulyan's beta.
            return coord_stat(g.reshape(W, -1), op="meamed", f=2 * cfg.f,
                              impl=cfg.impl, mask=sel_f
                              ).reshape(g.shape[1:])

        with jax.named_scope("coord_stats"):
            d = jax.tree.map(one_masked, tree)
        return d, {"weights": sel_f / jnp.maximum(theta, 1)}

    raise KeyError(f"unknown aggregator {cfg.name!r}; have "
                   f"{sorted(GRAM_RULES | COORDWISE_RULES | {'bulyan'})}")


# ---------------------------------------------------------------------------
# codec x aggregator bridge (the worker->server compressed path)
# ---------------------------------------------------------------------------

@contract(fp32_contractions=True, no_host_transfers=True, mask_traced=True,
          no_full_width=True)
def compressed_aggregate(tree, cfg: AggregatorConfig,
                         comm: CommConfig = CommConfig(), ef=None, *,
                         mask=None, sharded=None):
    """Aggregate through a worker->server compression codec.

    Carries the same graph contract as :func:`aggregate_tree` (fp32
    contractions, no host transfers, traced mask, no per-device full
    coordinate width under a mesh), extended over the codec
    encode/decode and EF stages.

    Routing (see docs/compression.md for the dataflow diagrams):

    * ``comm.codec == 'none'`` — plain :func:`aggregate_tree`; the dense
      gradient tree is "the payload" (``comm_bits`` = fp32 baseline).
    * gram-feeding codec (CountSketch) x linear-combination rule — the
      *payload* forms the Gram estimate (``tree_gram`` over ``(W, k)``
      sketch leaves) and :func:`aggregate_tree` runs with ``gram=``: worker
      selection/weighting happens entirely on compressed representations,
      the combine is a weighted all-reduce of the workers' own exact
      gradients, and no decoded ``(W, n)`` stack is ever materialized
      (asserted via hlo_stats in ``tests/test_comm.py``).  Error feedback
      does not apply — the update direction is exact given the weights —
      so an *explicit* ``error_feedback=True`` opts out of this path and
      runs EF-compensated decode instead (EF on an untouched gram path
      would be a dead buffer pretending to be active).
    * everything else — EF-compensated encode/decode
      (:func:`repro.comm.error_feedback.ef_encode_decode`) followed by
      :func:`aggregate_tree` on the decoded worker-major estimates.

    Args:
      tree: worker-major gradient pytree, every leaf shaped ``(W, ...)``.
      cfg: aggregation rule config.
      comm: codec selection + hyper-parameters.
      ef: worker-major EF memory (``repro.comm.error_feedback.init_ef``)
        or ``None``.  Required iff ``comm.wants_ef``.
      mask: optional (W,) active-worker membership (see
        :mod:`repro.dist.membership`), forwarded to
        :func:`aggregate_tree`.  Inactive workers ship no bits
        (``comm_bits`` scales by the active fraction) and their EF memory
        is frozen, not updated, until they rejoin.
      sharded: forwarded to :func:`aggregate_tree` — mesh-shard the
        gradient coordinate axis (see :mod:`repro.dist.sharded`).  The
        sketch-Gram of a gram-feeding codec stays unsharded (payload
        leaves are ``(W, k)`` with k tiny by construction); everything
        n-sized — the decode, the dense Gram, the combine — runs
        shard-local.
    Returns:
      ``(d_tree, aux, new_ef)``; ``aux`` extends the aggregator aux with
      ``comm_bits`` (total bits shipped worker->server this step, from the
      codec's declared cost model) and ``comm_ratio`` (dense fp32 bits /
      ``comm_bits``).  ``new_ef`` is ``None`` iff ``ef`` was.
    """
    codec = get_codec(comm)
    bits_dense = dense_bits(tree)
    W = jax.tree.leaves(tree)[0].shape[0]
    # active fraction: the per-step cost model is per-worker-uniform, so an
    # absent worker's share simply doesn't travel.
    frac = (jnp.asarray(1.0) if mask is None
            else jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0) / W)
    if codec is None:
        d, aux = aggregate_tree(tree, cfg, mask=mask, sharded=sharded)
        return d, {**aux, "comm_bits": jnp.asarray(bits_dense) * frac,
                   "comm_ratio": jnp.asarray(1.0)}, ef
    if comm.wants_ef and ef is None:
        raise ValueError(
            f"codec {comm.codec!r} needs error feedback: pass "
            "ef=repro.comm.init_ef(params, workers) and thread the "
            "returned state (or set CommConfig(error_feedback=False))")

    bits = codec.bits(tree)
    stats = {"comm_bits": jnp.asarray(bits) * frac,
             "comm_ratio": jnp.asarray(bits_dense / bits)}

    if codec.gram_feed and cfg.name in GRAM_RULES and not comm.wants_ef:
        with jax.named_scope("codec"):
            payload = codec.encode(tree)
        K = tree_gram(payload, gram_dtype=cfg.gram_dtype, impl=cfg.impl)
        d, aux = aggregate_tree(tree, cfg, gram=K, mask=mask,
                                sharded=sharded)
        return d, {**aux, **stats}, ef

    use_ef = ef if comm.wants_ef else None
    with jax.named_scope("codec"):
        decoded, _, new_ef = ef_encode_decode(codec, tree, use_ef, mask=mask)
    d, aux = aggregate_tree(decoded, cfg, mask=mask, sharded=sharded)
    return d, {**aux, **stats}, (new_ef if comm.wants_ef else ef)
