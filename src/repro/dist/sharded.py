"""Mesh-sharded aggregation: partial-Gram psum, shard-local everything else.

:mod:`repro.dist.aggregation` never materializes the flat ``(W, n)``
gradient stack — but it still assumes the whole worker-major pytree lives
on a *single device*.  This module removes that assumption.  The key fact
is Gram additivity over any coordinate partition:

    K = G G^T = sum_s  G[:, s] G[:, s]^T        (s = coordinate shards)

so aggregation decomposes into three stages with *one* tiny collective:

1. **partial Gram, shard-local** — each device holds a coordinate shard
   ``(W, n / n_shards)`` of every leaf and computes its partial Gram with
   the same fused chunk schedule as the single-device path
   (``repro.kernels.gram``), then ``psum``s the ``(W, W)`` result over the
   mesh axes.  ``W * W`` floats is the entire wire traffic.
2. **weights, replicated** — the rule's weight computation (the rank-p
   IRLS for FA, Weiszfeld, Krum scores, ...) sees only the psum'd Gram.
   It is O(p^3) with p = W, so running it replicated on every device is
   cheaper than any attempt to distribute it.
3. **combine, shard-local** — ``d = sum_w c_w g_w`` is per-coordinate, so
   each device combines its own shard; coordinate-wise rules (median /
   trimmed mean / MeaMed / Phocas, Bulyan's final stage) are *also*
   per-coordinate and run shard-local with zero communication.

The full unsharded stack therefore never exists on any device: the only
cross-device values are the ``(W, W)`` Gram and the ``(W,)`` weight
vector (asserted via post-partition HLO shape inspection in
``tests/test_sharded_agg.py``).

Layout: every leaf ``(W, ...)`` is viewed as ``(W, n_shards, chunk)``
(zero-padded up to a multiple of ``n_shards`` — padding contributes 0 to
the Gram and is sliced off after the combine) with the middle axis
sharded over *all* mesh axes, i.e. ``P(None, ('data', 'model'), None)``
on the production mesh.  ``shard_map`` then hands each device its
``(W, 1, chunk)`` block.  Equivalence with the single-device path is
exact for the combine (bit-identical given the same weights — the
per-coordinate reduction order over workers is unchanged) and fp32-
rounding-exact for the Gram (the psum reassociates the coordinate sum).

``sketch_stride`` composes: each shard samples its *local* chunk stream
with the shared ``chunk_schedule``, so the sketch subset is per-shard
deterministic (it differs from the single-device subset — both are
unbiased estimates of the same Gram).

Entry point: ``aggregate_tree(..., sharded=mesh)`` /
``compressed_aggregate(..., sharded=True)`` route here — see
:func:`sharded_aggregate_tree` and docs/sharded_aggregation.md.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.contract import contract

__all__ = ["coord_axes", "n_coord_shards", "sharded_tree_gram",
           "sharded_tree_combine", "sharded_aggregate_tree"]


def coord_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes the gradient coordinate dim shards over: all of them.

    The Gram psum reduces over the whole mesh, so there is no reason to
    leave an axis out — a ``(pod, data, model)`` mesh shards coordinates
    ``pod * data * model`` ways.
    """
    return tuple(mesh.axis_names)


def n_coord_shards(mesh: Mesh, axes: tuple[str, ...] | None = None) -> int:
    axes = coord_axes(mesh) if axes is None else axes
    return math.prod(mesh.shape[a] for a in axes)


def _to_view(leaf: jnp.ndarray, shards: int):
    """(W, ...) leaf -> ((W, shards, chunk) device view, flat width n)."""
    M = leaf.reshape(leaf.shape[0], -1)
    n = M.shape[1]
    chunk = -(-n // shards)
    pad = shards * chunk - n
    if pad:
        M = jnp.pad(M, ((0, 0), (0, pad)))
    return M.reshape(M.shape[0], shards, chunk), n


def _from_view(out: jnp.ndarray, n: int, shape: tuple[int, ...],
               mesh: Mesh, axes: tuple[str, ...]):
    """(shards, chunk) combined output -> original trailing leaf shape.

    The flat form keeps its sharding constraint whenever the slice is a
    no-op (no padding was added), so a cleanly-divisible stack stays
    sharded end to end; padded leaves pay one boundary reshard.
    """
    flat = out.reshape(-1)
    if flat.shape[0] == n:
        flat = jax.lax.with_sharding_constraint(
            flat, NamedSharding(mesh, P(axes)))
    else:
        flat = flat[:n]
    return flat.reshape(shape)


def _views(leaves, mesh: Mesh, axes: tuple[str, ...]):
    shards = n_coord_shards(mesh, axes)
    views, ns = [], []
    spec = NamedSharding(mesh, P(None, axes, None))
    for leaf in leaves:
        v, n = _to_view(leaf, shards)
        views.append(jax.lax.with_sharding_constraint(v, spec))
        ns.append(n)
    return views, ns


def _leafwise_shard_map(leaves, mesh: Mesh, axes: tuple[str, ...], fn,
                        *extras):
    """Run ``fn((W, n_local) matrix, *extras) -> (n_local,)`` per leaf
    inside one ``shard_map`` over the coordinate shards.

    ``extras`` are replicated inputs (weights, masks, selections).
    Returns the per-leaf worker-reduced arrays in the leaves' original
    trailing shapes.
    """
    views, ns = _views(leaves, mesh, axes)
    W = leaves[0].shape[0]
    spec_in = P(None, axes, None)
    spec_out = P(axes, None)

    def local(extras_, *xs):
        return tuple(fn(x.reshape(W, -1), *extras_).reshape(1, -1)
                     for x in xs)

    outs = jax.shard_map(local, mesh=mesh,
                         in_specs=(P(),) + (spec_in,) * len(views),
                         out_specs=(spec_out,) * len(views),
                         check_vma=False)(tuple(extras), *views)
    return [_from_view(o, n, leaf.shape[1:], mesh, axes)
            for o, n, leaf in zip(outs, ns, leaves)]


def sharded_tree_gram(tree, mesh: Mesh, *, sketch_stride: int = 1,
                      gram_dtype: str = "float32", impl: str = "xla",
                      axes: tuple[str, ...] | None = None) -> jnp.ndarray:
    """(W, W) Gram of a coordinate-sharded worker-major pytree.

    Each device runs the fused single-device ``tree_gram`` on its local
    ``(W, chunk)`` shards (same kernel, same chunk schedule, applied to
    the local stream) and the partial Grams meet in one ``psum``.

    Args:
      tree: worker-major pytree, every leaf shaped ``(W, ...)``.
      mesh: the mesh whose devices hold the coordinate shards.
      sketch_stride: per-shard chunk sampling (see module docstring).
      gram_dtype / impl: forwarded to the per-shard ``tree_gram``.
      axes: mesh axes to shard coordinates over (default: all).
    Returns:
      ``(W, W)`` fp32 Gram, replicated (an unsharded global array).
    """
    from repro.dist.aggregation import tree_gram
    leaves = jax.tree.leaves(tree)
    if not leaves:
        raise ValueError("sharded_tree_gram: empty gradient pytree")
    axes = coord_axes(mesh) if axes is None else axes
    W = leaves[0].shape[0]
    spec_in = P(None, axes, None)

    def local(*xs):
        K = tree_gram([x.reshape(W, -1) for x in xs], sketch_stride,
                      gram_dtype=gram_dtype, impl=impl)
        return jax.lax.psum(K, axes)

    with jax.named_scope("gram"):
        views, _ = _views(leaves, mesh, axes)
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(spec_in,) * len(views),
                             out_specs=P(), check_vma=False)(*views)


def sharded_tree_combine(tree, c: jnp.ndarray, mesh: Mesh, *,
                         impl: str = "xla",
                         axes: tuple[str, ...] | None = None):
    """Shard-local ``d = sum_w c_w g_w``: zero cross-device traffic.

    The combine is per-coordinate, so each device reduces the worker axis
    of its own shard; given identical weights the result is bit-identical
    to the single-device ``tree_combine`` (same per-coordinate reduction).

    Args:
      tree: worker-major pytree, every leaf shaped ``(W, ...)``.
      c: ``(W,)`` combination weights (replicated).
      mesh / axes: coordinate-shard layout (default: all mesh axes).
      impl: kernel backend for the per-shard combine.
    Returns:
      Pytree with the worker axis reduced away, coordinate-sharded.
    """
    from repro.dist.aggregation import tree_combine
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        raise ValueError("sharded_tree_combine: empty gradient pytree")
    axes = coord_axes(mesh) if axes is None else axes

    def one(M, c_):
        return tree_combine([M], c_, impl=impl)[0]

    with jax.named_scope("combine"):
        outs = _leafwise_shard_map(leaves, mesh, axes, one, c)
    return treedef.unflatten(outs)


@contract(fp32_contractions=True, no_host_transfers=True, mask_traced=True,
          no_full_width=True, kernel_race=True, kernel_budget=True)
def sharded_aggregate_tree(tree, cfg, *, mesh: Mesh, gram=None, mask=None):
    """Mesh-sharded :func:`repro.dist.aggregation.aggregate_tree`.

    Same contract and return value as the single-device path (including
    ``gram=`` / ``mask=`` composition) with the dataflow of the module
    docstring: psum'd partial Grams, replicated weights, shard-local
    combine / coordinate rules.  Call through
    ``aggregate_tree(..., sharded=...)`` rather than directly.
    """
    from repro.dist import aggregation as agg
    from repro.core import aggregators

    leaves, treedef = jax.tree.flatten(tree)
    W = leaves[0].shape[0]
    axes = coord_axes(mesh)

    def psummed_gram():
        if gram is not None:
            return gram
        return sharded_tree_gram(tree, mesh, sketch_stride=cfg.sketch_stride,
                                 gram_dtype=cfg.gram_dtype, impl=cfg.impl,
                                 axes=axes)

    if cfg.name in agg.GRAM_RULES:
        K = psummed_gram()
        # Weight computation on the (W, W) Gram: replicated by SPMD — at
        # O(p^3), p = W, this is cheaper everywhere than distributing it.
        c, aux = agg._gram_weights(K, cfg, mask)
        d = sharded_tree_combine(tree, c, mesh, impl=cfg.impl, axes=axes)
        return d, {**aux, "weights": c}

    if cfg.name in agg.COORDWISE_RULES:
        # Coordinate-wise rules commute with the coordinate sharding:
        # each device applies the rule to its own shard, no communication.
        # coord_stat dispatches cfg.impl per shard — the per-coordinate
        # math is independent of the shard blocking, so the sharded result
        # is bit-identical to single-device on either backend.
        from repro.kernels.coord_stats.ops import coord_stat
        if mask is None:
            with jax.named_scope("coord_stats"):
                outs = _leafwise_shard_map(
                    leaves, mesh, axes,
                    lambda M: coord_stat(M, op=cfg.name, f=cfg.f,
                                         impl=cfg.impl))
            return treedef.unflatten(outs), {
                "weights": jnp.full((W,), 1.0 / W, jnp.float32)}
        with jax.named_scope("coord_stats"):
            outs = _leafwise_shard_map(
                leaves, mesh, axes,
                lambda M, m: coord_stat(M, op=cfg.name, f=cfg.f,
                                        impl=cfg.impl, mask=m), mask)
        wa = jnp.maximum(jnp.sum(mask), 1.0)
        return treedef.unflatten(outs), {"weights": mask / wa}

    if cfg.name == "bulyan":
        # Selection is Gram-only (replicated); the trimmed mean over the
        # selected workers is coordinate-wise (shard-local).
        K = psummed_gram()
        from repro.kernels.coord_stats.ops import bulyan_select, coord_stat
        if mask is None:
            with jax.named_scope("solve"):
                D2 = aggregators.sq_dists_from_gram(K)
                picks = bulyan_select(D2, f=cfg.f, impl=cfg.impl)
            theta = picks.shape[0]

            # Bulyan's coordinate stage == MeaMed with f' = 2f on the
            # selected stack (keep-count max(theta - 2f, 1) = beta).
            def one(M, picks_):
                return coord_stat(M[picks_], op="meamed", f=2 * cfg.f,
                                  impl=cfg.impl)

            with jax.named_scope("coord_stats"):
                outs = _leafwise_shard_map(leaves, mesh, axes, one, picks)
            with jax.named_scope("solve"):
                c = jnp.zeros((W,), jnp.float32).at[picks].add(1.0 / theta)
            return treedef.unflatten(outs), {"weights": c}

        with jax.named_scope("solve"):
            D2 = aggregators.sq_dists_from_gram(K)
            selected, theta = aggregators.masked_bulyan_select(D2, cfg.f,
                                                               mask)
            sel_f = selected.astype(jnp.float32)

        def one_masked(M, sel):
            # masked MeaMed with W_a = theta: keep-count max(theta-2f, 1).
            return coord_stat(M, op="meamed", f=2 * cfg.f, impl=cfg.impl,
                              mask=sel)

        with jax.named_scope("coord_stats"):
            outs = _leafwise_shard_map(leaves, mesh, axes, one_masked, sel_f)
        return treedef.unflatten(outs), {
            "weights": sel_f / jnp.maximum(theta, 1)}

    raise KeyError(f"unknown aggregator {cfg.name!r}")
