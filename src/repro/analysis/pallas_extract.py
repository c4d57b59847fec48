"""Recover the structure of every ``pallas_call`` inside a traced jaxpr.

The jaxpr/HLO rules of :mod:`repro.analysis.rules` treat a
``pallas_call`` as an opaque primitive: its grid, BlockSpecs, index maps
and kernel body never cross the equation boundary, so none of the
invariants the kernel docstrings promise (guarded accumulation, inert
padding, finite sentinels) were enforced by anything.  This module is
the substrate that opens the box:

* :func:`find_pallas_calls` walks a jaxpr (through pjit / cond / scan /
  shard_map bodies) and returns one :class:`PallasSite` per call with
  the grid, per-operand :class:`Block` descriptors (block shape,
  operand shape, dtype, index-map jaxpr) and the raw kernel body jaxpr.
* :meth:`PallasSite.visits` **concretely evaluates** every index map
  over the full grid product — grids here are small and static (the
  chunk schedules of the production kernels), so exhaustive evaluation
  is exact where symbolic reasoning would have to approximate.  From the
  visit map, :meth:`PallasSite.dependent_axes` recovers which grid axes
  an operand's block index actually depends on; the complement (axes the
  map ignores, with extent > 1) are the *revisit* axes — the grid steps
  that hit the same output block again, i.e. exactly the steps a
  race/accumulation rule must reason about.

The rule families themselves (KTILING / KRACE / KVMEM / KPRECISION /
KSENTINEL) live in :mod:`repro.analysis.pallas_rules`.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from functools import cached_property

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jax_core

__all__ = ["Block", "PallasSite", "find_pallas_calls", "grid_points",
           "MAX_GRID_POINTS"]

# Exhaustive index-map evaluation is exact but linear in the grid
# product; production grids are O(n / block_n) ~ hundreds of steps.  A
# grid beyond this bound is almost certainly a shape bug upstream — the
# analyzer refuses rather than silently sampling.
MAX_GRID_POINTS = 1 << 16


def grid_points(grid: tuple[int, ...]):
    """Iterate the full grid product in row-major order."""
    return itertools.product(*(range(g) for g in grid))


def _int_block_shape(block_shape) -> tuple[int, ...]:
    """BlockSpec dims as plain ints (``Blocked(n)`` -> n; squeezed,
    element and mapped dims count as 1)."""
    return tuple(d if isinstance(d, int) else getattr(d, "block_size", 1)
                 for d in block_shape)


@dataclass(frozen=True)
class Block:
    """One operand of a ``pallas_call``: its tiling and index map.

    ``array_shape`` is the shape of the operand the caller actually
    passed (after any padding the wrapper does), so in-bounds reasoning
    over ``block_shape`` x index map is exact.
    """

    role: str                               # "in" | "out"
    position: int                           # operand index within role
    block_shape: tuple[int, ...]
    array_shape: tuple[int, ...]
    dtype: jnp.dtype
    index_map: jax_core.ClosedJaxpr

    @property
    def block_bytes(self) -> int:
        size = 1
        for d in self.block_shape:
            size *= d
        return size * jnp.dtype(self.dtype).itemsize

    def grid_blocks(self) -> tuple[int, ...]:
        """Number of blocks covering the array along each dim (ceil)."""
        return tuple(-(-a // b) for a, b in
                     zip(self.array_shape, self.block_shape))


def _eval_structural(closed: jax_core.ClosedJaxpr):
    """Fast path for equation-free index maps (``lambda i, j: (j, 0)``).

    The outvars of an eqn-free jaxpr are a mix of invars and literals —
    the common case for every production kernel — so each grid point
    evaluates in pure Python with no dispatch.
    Returns None when the map actually computes something.
    """
    jaxpr = closed.jaxpr
    if jaxpr.eqns:
        return None
    positions = {v: i for i, v in enumerate(jaxpr.invars)}
    slots = []
    for ov in jaxpr.outvars:
        if isinstance(ov, jax_core.Literal):
            slots.append(("lit", int(ov.val)))
        elif ov in positions:
            slots.append(("arg", positions[ov]))
        else:
            return None                      # a constvar: fall back

    def run(idx):
        return tuple(v if tag == "lit" else idx[v] for tag, v in slots)
    return run


def _eval_vectorized(closed: jax_core.ClosedJaxpr, grid):
    """Evaluate a computing index map over the whole grid in one jitted
    vmap (one compile total, vs one eval_jaxpr dispatch chain per point)."""
    pts = np.asarray(list(grid_points(grid)), dtype=np.int32)
    if pts.size == 0:
        return {}

    def one(row):
        outs = jax.core.eval_jaxpr(closed.jaxpr, closed.consts,
                                   *[row[i] for i in range(pts.shape[1])])
        return tuple(jnp.asarray(o, jnp.int32) for o in outs)

    cols = jax.jit(jax.vmap(one))(jnp.asarray(pts))
    cols = [np.asarray(c) for c in cols]
    return {tuple(int(x) for x in pts[r]):
            tuple(int(c[r]) for c in cols)
            for r in range(pts.shape[0])}


@dataclass
class PallasSite:
    """One discovered ``pallas_call``, ready for the kernel rules."""

    name: str                               # kernel function name
    scope: str                              # jaxpr path to the call
    grid: tuple[int, ...]
    inputs: tuple[Block, ...]
    outputs: tuple[Block, ...]
    scratch_shapes: tuple[tuple[tuple[int, ...], jnp.dtype], ...]
    kernel: jax_core.Jaxpr                  # kernel body (refs as invars)
    num_index_operands: int
    input_output_aliases: tuple[tuple[int, int], ...]
    interpret: bool = False
    _visit_cache: dict = field(default_factory=dict, repr=False)

    @property
    def blocks(self) -> tuple[Block, ...]:
        return self.inputs + self.outputs

    @cached_property
    def n_grid_points(self) -> int:
        n = 1
        for g in self.grid:
            n *= g
        return n

    def kernel_refs(self, role: str) -> list:
        """Kernel-jaxpr invars holding the refs of ``role``
        (``in`` | ``out`` | ``scratch``), in operand order."""
        iv = list(self.kernel.invars)
        n_idx = self.num_index_operands
        n_in, n_out = len(self.inputs), len(self.outputs)
        if role == "in":
            return iv[n_idx:n_idx + n_in]
        if role == "out":
            return iv[n_idx + n_in:n_idx + n_in + n_out]
        if role == "scratch":
            return iv[n_idx + n_in + n_out:]
        raise ValueError(role)

    def visits(self, block: Block) -> dict[tuple[int, ...],
                                           list[tuple[int, ...]]]:
        """block index -> ordered list of grid points that map to it.

        Exact: every grid point of the (static) grid is evaluated
        through the operand's index map.
        """
        key = (block.role, block.position)
        if key in self._visit_cache:
            return self._visit_cache[key]
        if self.n_grid_points > MAX_GRID_POINTS:
            raise ValueError(
                f"pallas_call {self.name!r}: grid {self.grid} has "
                f"{self.n_grid_points} points > MAX_GRID_POINTS "
                f"({MAX_GRID_POINTS}); exhaustive index-map evaluation "
                "refused — shrink the analysis shapes")
        fast = _eval_structural(block.index_map)
        if fast is not None:
            mapping = {idx: fast(idx) for idx in grid_points(self.grid)}
        else:
            mapping = _eval_vectorized(block.index_map, self.grid)
        out: dict[tuple[int, ...], list[tuple[int, ...]]] = {}
        for gidx in grid_points(self.grid):
            out.setdefault(mapping[gidx], []).append(gidx)
        self._visit_cache[key] = out
        return out

    def dependent_axes(self, block: Block) -> set[int]:
        """Grid axes the block index actually depends on.

        Axis ``a`` is dependent iff two grid points differing *only* in
        ``a`` map to different block indices.  Because the full product
        is evaluated, a map constant along every single-axis line within
        a fiber is constant on the whole fiber — so grid points sharing
        a projection onto the dependent axes provably share a block.
        """
        visits = self.visits(block)
        point_to_block = {g: b for b, pts in visits.items() for g in pts}
        dependent: set[int] = set()
        for axis in range(len(self.grid)):
            if self.grid[axis] <= 1:
                continue
            seen: dict[tuple, tuple] = {}
            for gidx, bidx in point_to_block.items():
                proj = gidx[:axis] + gidx[axis + 1:]
                if proj in seen:
                    if seen[proj] != bidx:
                        dependent.add(axis)
                        break
                else:
                    seen[proj] = bidx
        return dependent

    def revisit_axes(self, block: Block) -> set[int]:
        """Grid axes (extent > 1) along which the same block is hit
        again — the axes an accumulation/race rule must reason about."""
        dep = self.dependent_axes(block)
        return {a for a in range(len(self.grid))
                if self.grid[a] > 1 and a not in dep}


def _kernel_fn_name(eqn) -> str:
    info = eqn.params.get("name_and_src_info")
    name = getattr(info, "name", None)
    return name or "pallas_call"


def _site_from_eqn(eqn, scope: str) -> PallasSite:
    gm = eqn.params["grid_mapping"]
    kernel = eqn.params["jaxpr"]
    if isinstance(kernel, jax_core.ClosedJaxpr):
        kernel = kernel.jaxpr
    mappings = list(gm.block_mappings)
    blocks: list[Block] = []
    for i, bm in enumerate(mappings):
        role = "in" if i < gm.num_inputs else "out"
        pos = i if role == "in" else i - gm.num_inputs
        sds = bm.array_aval
        blocks.append(Block(
            role=role, position=pos,
            block_shape=_int_block_shape(bm.block_shape),
            array_shape=tuple(int(d) for d in sds.shape),
            dtype=jnp.dtype(sds.dtype),
            index_map=bm.index_map_jaxpr))
    n_ref = gm.num_index_operands + gm.num_inputs + gm.num_outputs
    scratch = []
    for v in kernel.invars[n_ref:]:
        aval = getattr(v.aval, "inner_aval", v.aval)
        scratch.append((tuple(int(d) for d in getattr(aval, "shape", ())),
                        jnp.dtype(getattr(aval, "dtype", jnp.float32))))
    aliases = tuple(sorted(dict(eqn.params.get(
        "input_output_aliases", ())).items()))
    return PallasSite(
        name=_kernel_fn_name(eqn), scope=scope,
        grid=tuple(int(g) for g in gm.grid),
        inputs=tuple(b for b in blocks if b.role == "in"),
        outputs=tuple(b for b in blocks if b.role == "out"),
        scratch_shapes=tuple(scratch), kernel=kernel,
        num_index_operands=int(gm.num_index_operands),
        input_output_aliases=aliases,
        interpret=bool(eqn.params.get("interpret", False)))


def find_pallas_calls(jaxpr) -> list[PallasSite]:
    """Every ``pallas_call`` reachable from ``jaxpr`` (a ``Jaxpr`` or
    ``ClosedJaxpr``), in traversal order, through pjit / control-flow /
    shard_map sub-jaxprs."""
    from repro.analysis.rules import iter_eqns

    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    sites = []
    for eqn, scope in iter_eqns(jaxpr):
        if eqn.primitive.name == "pallas_call":
            sites.append(_site_from_eqn(eqn, scope))
    return sites
