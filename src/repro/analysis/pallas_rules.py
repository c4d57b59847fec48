"""Kernel-level rule families over extracted ``pallas_call`` sites.

PR 7's linter stops at the jaxpr/HLO graph level; these rules descend
into the kernels themselves via :mod:`repro.analysis.pallas_extract`.
Families (``K`` prefix = kernel-level; catalog in
docs/static_analysis.md):

``ktiling``
    Every output block is covered by the grid, every visited block
    starts inside its operand (a ragged edge block may run past the
    end), and each output block is written
    by exactly one grid index along the axes its index map depends on —
    overlap along a dependent (non-revisit) axis means two unrelated
    grid steps race on the same tile.
``krace``
    An output block revisited across grid steps must follow the
    guarded-accumulation idiom (flash_attn's k axis, the tree Gram's
    chunk axis): a write predicated on the first visiting step
    initializes the tile, and every unconditional write must derive
    from a prior read of the same ref (accumulate, never clobber).
    Writing an input ref without a declared ``input_output_alias`` —
    or declaring one whose index maps disagree — is also a race.
``kvmem``
    The per-grid-step VMEM working set (double-buffered block bytes +
    scratch) must fit a configurable budget, and block shapes must be
    lane/sublane aligned (or span the full array dim) for their dtype.
``kprecision``
    PR 7's PRECISION rule applied *inside* kernel bodies — bf16/fp16
    MXU contractions must carry ``preferred_element_type=f32`` — plus a
    kernel-only obligation: a revisited-and-read output ref is a
    cross-step accumulator and must be fp32.
``ksentinel``
    Masked kernels must use *finite* sentinels (``-1e30`` /
    ``finfo.max``, never ``+-inf``: inf-inf arithmetic inside the
    revisit loop manufactures NaNs that a mask can no longer remove),
    and must consume the membership mask as a traced ref operand.
"""

from __future__ import annotations

import dataclasses
import itertools
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np
from jax.extend import core as jax_core

from repro.analysis.findings import Finding
from repro.analysis.pallas_extract import (Block, PallasSite,
                                           find_pallas_calls)

__all__ = ["check_kernel_tiling", "check_kernel_race", "check_kernel_vmem",
           "check_kernel_precision", "check_kernel_sentinel",
           "check_kernels", "sites_of", "VMEM_BUDGET_BYTES", "K_RULES"]

# Per-core VMEM on current TPUs is ~16 MiB; the default budget leaves
# headroom for Mosaic's own spills.
VMEM_BUDGET_BYTES = 12 * 2 ** 20

_LOW = (jnp.bfloat16, jnp.float16)


def _is_low(dtype) -> bool:
    return any(jnp.dtype(dtype) == jnp.dtype(d) for d in _LOW)


def sites_of(graph_or_jaxpr) -> list[PallasSite]:
    """Accept a :class:`repro.analysis.rules.Graph`, a jaxpr, or a
    pre-extracted site list."""
    if isinstance(graph_or_jaxpr, list):
        return graph_or_jaxpr
    jaxpr = getattr(graph_or_jaxpr, "jaxpr", graph_or_jaxpr)
    if jaxpr is None:
        raise ValueError("kernel rules need a traced jaxpr (HLO has "
                         "already erased the pallas_call structure)")
    return find_pallas_calls(jaxpr)


def _blk(site: PallasSite, block: Block) -> str:
    return f"{site.name}/{block.role}[{block.position}]"


# ---------------------------------------------------------------------------
# KTILING
# ---------------------------------------------------------------------------

def check_kernel_tiling(graph_or_sites, *, name: str = "") -> list[Finding]:
    """KTILING: coverage, bounds, and single-writer tiling soundness."""
    findings: list[Finding] = []
    for site in sites_of(graph_or_sites):
        for block in site.blocks:
            visits = site.visits(block)
            for bidx in visits:
                # A ragged edge block (it starts inside the operand and
                # runs past its end) is legal Pallas: the overhang is
                # padded on read and dropped on write.  A block that
                # starts outside the operand is not.
                oob = [k for k, (b, bs, a) in enumerate(
                    zip(bidx, block.block_shape, block.array_shape))
                    if b < 0 or b * bs >= a]
                if oob:
                    g0 = visits[bidx][0]
                    findings.append(Finding(
                        "ktiling", "oob-block", site.scope,
                        f"{_blk(site, block)} block {bidx} @ grid {g0}",
                        f"{_blk(site, block)}: block index {bidx} x block "
                        f"shape {block.block_shape} overruns the "
                        f"operand {block.array_shape} along dim(s) {oob} — "
                        "the kernel reads/writes out of bounds"))
            if block.role != "out":
                continue
            nblocks = block.grid_blocks()
            want = set(itertools.product(*(range(n) for n in nblocks)))
            missing = sorted(want - set(visits))
            if missing:
                findings.append(Finding(
                    "ktiling", "uncovered-block", site.scope,
                    f"{_blk(site, block)} missing {missing[:4]}"
                    f"{'...' if len(missing) > 4 else ''}",
                    f"{_blk(site, block)}: {len(missing)} of "
                    f"{len(want)} output block(s) are never written by "
                    "any grid step — the result carries uninitialized "
                    "memory"))
            dep = sorted(site.dependent_axes(block))
            for bidx, pts in visits.items():
                projs = {tuple(g[a] for a in dep) for g in pts}
                if len(projs) > 1:
                    findings.append(Finding(
                        "ktiling", "overlapping-tiles", site.scope,
                        f"{_blk(site, block)} block {bidx} <- grid "
                        f"projections {sorted(projs)[:4]}",
                        f"{_blk(site, block)}: output block {bidx} is "
                        f"written by {len(projs)} distinct grid indices "
                        f"along non-revisit axes {dep} — overlapping "
                        "tiles race on the same output"))
                    break                    # one finding per block map
    return findings


# ---------------------------------------------------------------------------
# kernel-body dataflow (shared by KRACE / KPRECISION / KSENTINEL)
# ---------------------------------------------------------------------------

_EMPTY = (frozenset(), frozenset())


def _union(*taints):
    axes: frozenset = frozenset()
    reads: frozenset = frozenset()
    for a, r in taints:
        axes |= a
        reads |= r
    return (axes, reads)


@dataclass
class _Access:
    ref: object                             # root kernel invar Var
    kind: str                               # "read" | "write" | "accum"
    conditional: bool
    guard_axes: frozenset                   # pid axes tainting the guard
    value_reads: frozenset                  # refs whose reads feed the value
    scope: str


def _walk_kernel(jaxpr, env, refmap, guard, scope, accesses):
    """Forward dataflow over a kernel (sub-)jaxpr.

    ``env`` maps vars to (pid-axes, refs-read) taints; ``refmap`` maps
    ref-typed vars to their root kernel invar; ``guard`` is the taint of
    the enclosing cond predicates (None at top level).  Returns the
    taints of the jaxpr's outvars.
    """
    def taint(v):
        if isinstance(v, jax_core.Literal):
            return _EMPTY
        return env.get(v, _EMPTY)

    def set_out(eqn, t):
        for ov in eqn.outvars:
            env[ov] = t

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        in_taints = [taint(v) for v in eqn.invars]
        if prim == "program_id":
            set_out(eqn, (frozenset({int(eqn.params["axis"])}),
                          frozenset()))
        elif prim == "get":
            ref = refmap.get(eqn.invars[0])
            if ref is not None:
                accesses.append(_Access(
                    ref, "read", guard is not None,
                    guard[0] if guard else frozenset(),
                    frozenset(), scope))
                set_out(eqn, _union(*in_taints,
                                    (frozenset(), frozenset({ref}))))
            else:
                set_out(eqn, _union(*in_taints))
        elif prim in ("swap", "addupdate"):
            ref = refmap.get(eqn.invars[0])
            val_taint = _union(*in_taints[1:])
            if ref is not None:
                accesses.append(_Access(
                    ref, "accum" if prim == "addupdate" else "write",
                    guard is not None,
                    guard[0] if guard else frozenset(),
                    val_taint[1], scope))
            set_out(eqn, (val_taint[0],
                          val_taint[1] | ({ref} if ref else set())))
        elif prim == "cond":
            pred_taint = in_taints[0]
            branch_guard = _union(pred_taint, guard or _EMPTY)
            outs = []
            for br in eqn.params["branches"]:
                sub = br.jaxpr if isinstance(br, jax_core.ClosedJaxpr) \
                    else br
                for sv, ov, t in zip(sub.invars, eqn.invars[1:],
                                     in_taints[1:]):
                    env[sv] = t
                    if not isinstance(ov, jax_core.Literal) \
                            and ov in refmap:
                        refmap[sv] = refmap[ov]
                outs.append(_walk_kernel(sub, env, refmap, branch_guard,
                                         scope + "/cond", accesses))
            merged = [_union(pred_taint, *[o[i] for o in outs])
                      for i in range(len(eqn.outvars))] or []
            for ov, t in zip(eqn.outvars, merged):
                env[ov] = t
        else:
            subs = [(k, v) for k, v in eqn.params.items()
                    if isinstance(v, (jax_core.Jaxpr,
                                      jax_core.ClosedJaxpr))]
            if not subs:
                set_out(eqn, _union(*in_taints))
                continue
            out_taint = _union(*in_taints)
            for key, sub in subs:
                sj = sub.jaxpr if isinstance(sub, jax_core.ClosedJaxpr) \
                    else sub
                # positional mapping: the trailing eqn invars line up
                # with the body invars (pjit/closed_call/scan exactly;
                # while bodies shifted by the cond consts — good enough
                # for ref identity, which is what the walk needs)
                ivs = eqn.invars[-len(sj.invars):] if sj.invars else []
                for sv, ov in zip(sj.invars, ivs):
                    env[sv] = taint(ov)
                    if not isinstance(ov, jax_core.Literal) \
                            and ov in refmap:
                        refmap[sv] = refmap[ov]
                sub_outs = _walk_kernel(sj, env, refmap, guard,
                                        f"{scope}/{prim}", accesses)
                if len(sub_outs) == len(eqn.outvars):
                    out_taint = _union(out_taint, *sub_outs)
            set_out(eqn, out_taint)
    return [taint(v) for v in jaxpr.outvars]


def _kernel_accesses(site: PallasSite) -> list[_Access]:
    refmap = {}
    for role in ("in", "out"):
        for v in site.kernel_refs(role):
            refmap[v] = v
    accesses: list[_Access] = []
    _walk_kernel(site.kernel, {}, refmap, None, site.scope, accesses)
    return accesses


# ---------------------------------------------------------------------------
# KRACE
# ---------------------------------------------------------------------------

def check_kernel_race(graph_or_sites, *, name: str = "") -> list[Finding]:
    """KRACE: revisited blocks must accumulate, never clobber."""
    findings: list[Finding] = []
    for site in sites_of(graph_or_sites):
        accesses = _kernel_accesses(site)
        in_refs = site.kernel_refs("in")
        out_refs = site.kernel_refs("out")
        aliased_inputs = {i for i, _ in site.input_output_aliases}

        for pos, ref in enumerate(in_refs):
            if pos in aliased_inputs:
                continue
            if any(a.ref is ref and a.kind in ("write", "accum")
                   for a in accesses):
                findings.append(Finding(
                    "krace", "input-write", site.scope,
                    f"{site.name}/in[{pos}]",
                    f"{site.name}: kernel writes input ref [{pos}] with "
                    "no declared input_output_alias — aliasing an "
                    "operand the pipeline may still be streaming is a "
                    "race"))

        for i_in, i_out in site.input_output_aliases:
            if i_in < len(site.inputs) and i_out < len(site.outputs):
                vin = site.visits(site.inputs[i_in])
                vout = site.visits(site.outputs[i_out])
                if vin != vout:
                    findings.append(Finding(
                        "krace", "alias-mismatch", site.scope,
                        f"{site.name} alias in[{i_in}]->out[{i_out}]",
                        f"{site.name}: declared input_output_alias "
                        f"({i_in} -> {i_out}) but the two index maps "
                        "visit different blocks — reads and writes of "
                        "the shared buffer interleave across grid "
                        "steps"))

        for pos, block in enumerate(site.outputs):
            ref = out_refs[pos]
            revisit = site.revisit_axes(block)
            if not revisit:
                continue
            ref_acc = [a for a in accesses if a.ref is ref]
            reads = [a for a in ref_acc if a.kind in ("read", "accum")]
            for a in ref_acc:
                if (a.kind == "write" and not a.conditional
                        and ref not in a.value_reads):
                    findings.append(Finding(
                        "krace", "unguarded-overwrite", a.scope,
                        f"{_blk(site, block)} revisited along axes "
                        f"{sorted(revisit)}",
                        f"{_blk(site, block)}: grid revisits this block "
                        f"along axes {sorted(revisit)} but the kernel "
                        "overwrites it unconditionally with a value "
                        "independent of the ref — later steps clobber "
                        "earlier ones; accumulate, or guard the write "
                        "with pl.when on the revisit step"))
                    break
            if reads and not any(
                    a.kind in ("write", "accum") and a.conditional
                    and a.guard_axes & revisit for a in ref_acc):
                findings.append(Finding(
                    "krace", "missing-init", site.scope,
                    f"{_blk(site, block)} revisited along axes "
                    f"{sorted(revisit)}",
                    f"{_blk(site, block)}: the kernel reads this "
                    "revisited accumulator but never writes it under a "
                    "first-visit predicate — the first grid step "
                    "consumes uninitialized VMEM; add "
                    "pl.when(pid == 0) initialization"))
    return findings


# ---------------------------------------------------------------------------
# KVMEM
# ---------------------------------------------------------------------------

_SUBLANE = {1: 32, 2: 16, 4: 8, 8: 8}


def check_kernel_vmem(graph_or_sites, *,
                      max_bytes: float = VMEM_BUDGET_BYTES,
                      name: str = "") -> list[Finding]:
    """KVMEM: per-grid-step working set + lane/sublane alignment."""
    findings: list[Finding] = []
    for site in sites_of(graph_or_sites):
        # Pallas double-buffers streamed blocks (compute on one while
        # the DMA fills the other); scratch is single-buffered.
        step = sum(2 * b.block_bytes for b in site.blocks)
        step += sum(int(np.prod(shape, dtype=np.int64)) * dt.itemsize
                    for shape, dt in site.scratch_shapes)
        if step > max_bytes:
            findings.append(Finding(
                "kvmem", "working-set", site.scope,
                " + ".join(f"{_blk(site, b)}{b.block_shape}"
                           for b in site.blocks),
                f"{site.name}: per-grid-step VMEM working set "
                f"{step / 2**20:.2f} MiB (double-buffered blocks + "
                f"scratch) exceeds the budget "
                f"{max_bytes / 2**20:.2f} MiB"))
        for block in site.blocks:
            bad = []
            bs, ar = block.block_shape, block.array_shape
            lane = 128
            sub = _SUBLANE.get(jnp.dtype(block.dtype).itemsize, 8)
            if bs and bs[-1] % lane and bs[-1] != ar[-1]:
                bad.append(f"lane dim {bs[-1]} (want %{lane} or full "
                           f"{ar[-1]})")
            if len(bs) >= 2 and bs[-2] % sub and bs[-2] != ar[-2]:
                bad.append(f"sublane dim {bs[-2]} (want %{sub} or full "
                           f"{ar[-2]})")
            if bad:
                findings.append(Finding(
                    "kvmem", "misaligned-block", site.scope,
                    f"{_blk(site, block)} block {bs} of array {ar} "
                    f"[{block.dtype}]",
                    f"{_blk(site, block)}: block shape {bs} breaks the "
                    f"{block.dtype} tiling constraint: {'; '.join(bad)} "
                    "— Mosaic pads each tile, silently inflating VMEM "
                    "and masking the arithmetic"))
    return findings


# ---------------------------------------------------------------------------
# KPRECISION
# ---------------------------------------------------------------------------

def check_kernel_precision(graph_or_sites, *,
                           name: str = "") -> list[Finding]:
    """KPRECISION: fp32 MXU accumulation + fp32 cross-step accumulators."""
    from repro.analysis.rules import Graph, check_precision

    findings: list[Finding] = []
    for site in sites_of(graph_or_sites):
        inner = check_precision(
            Graph(site.name, jax_core.ClosedJaxpr(site.kernel, ())))
        findings += [dataclasses.replace(f, rule="kprecision")
                     for f in inner]
        accesses = _kernel_accesses(site)
        out_refs = site.kernel_refs("out")
        for pos, block in enumerate(site.outputs):
            if not site.revisit_axes(block) or not _is_low(block.dtype):
                continue
            ref = out_refs[pos]
            if any(a.ref is ref and a.kind in ("read", "accum")
                   for a in accesses):
                findings.append(Finding(
                    "kprecision", "low-precision-accumulator",
                    site.scope,
                    f"{_blk(site, block)} dtype={block.dtype}",
                    f"{_blk(site, block)}: this ref carries state "
                    "across revisiting grid steps but is "
                    f"{jnp.dtype(block.dtype).name} — cross-step "
                    "accumulation loses mass every store; keep the "
                    "accumulator fp32 and cast once on the way out"))
    return findings


# ---------------------------------------------------------------------------
# KSENTINEL
# ---------------------------------------------------------------------------

def _nonfinite_literals(jaxpr, scope):
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if isinstance(v, jax_core.Literal):
                val = np.asarray(v.val)
                if (np.issubdtype(val.dtype, np.floating)
                        and not np.all(np.isfinite(val))):
                    yield eqn, scope, val
        for p in eqn.params.values():
            ps = p if isinstance(p, (tuple, list)) else (p,)
            for sub in ps:
                if isinstance(sub, jax_core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax_core.Jaxpr):
                    yield from _nonfinite_literals(
                        sub, f"{scope}/{eqn.primitive.name}")


def check_kernel_sentinel(graph_or_sites, *, mask_inputs=None,
                          name: str = "") -> list[Finding]:
    """KSENTINEL: finite sentinels only; masks consumed as traced refs.

    ``mask_inputs``: input operand positions that carry a membership
    mask — each must actually be read by the kernel body (a mask that
    is accepted but ignored silently aggregates absent workers, the
    kernel-level twin of the MASK rule's ``<unused>`` finding).
    """
    findings: list[Finding] = []
    for site in sites_of(graph_or_sites):
        seen_vals: set = set()
        for eqn, scope, val in _nonfinite_literals(site.kernel,
                                                   site.scope):
            tag = (scope, float(np.ravel(val)[0]))
            if tag in seen_vals:
                continue
            seen_vals.add(tag)
            findings.append(Finding(
                "ksentinel", "nonfinite-sentinel", scope,
                f"{site.name}: {eqn.primitive.name} consumes literal "
                f"{np.ravel(val)[0]}",
                f"{site.name}: non-finite constant "
                f"{np.ravel(val)[0]} inside the kernel body — inf "
                "sentinels turn masked lanes into NaNs under "
                "subtraction/0*inf; use a finite sentinel "
                "(-1e30 / finfo.max)"))
        if mask_inputs:
            accesses = _kernel_accesses(site)
            in_refs = site.kernel_refs("in")
            for pos in mask_inputs:
                if pos >= len(in_refs):
                    continue
                ref = in_refs[pos]
                if not any(a.ref is ref and a.kind == "read"
                           for a in accesses):
                    findings.append(Finding(
                        "ksentinel", "mask-unread", site.scope,
                        f"{site.name}/in[{pos}]",
                        f"{site.name}: membership-mask operand "
                        f"[{pos}] is never read by the kernel body — "
                        "inactive workers would silently participate"))
    return findings


# ---------------------------------------------------------------------------
# composite entry point (what @contract and the sweep call)
# ---------------------------------------------------------------------------

def check_kernels(graph_or_jaxpr, *, vmem_budget: float = VMEM_BUDGET_BYTES,
                  mask_inputs=None, expect_sites: int | None = None,
                  name: str = "") -> list[Finding]:
    """Run every kernel rule family over the graph's pallas_call sites.

    ``expect_sites`` is detector sanity (mirrors SHAPE's
    ``require_dims``): a sweep entry that promises to lint N kernels but
    traces a graph with a different count is not looking at the graph it
    thinks it is.
    """
    sites = sites_of(graph_or_jaxpr)
    findings: list[Finding] = []
    if expect_sites is not None and len(sites) != expect_sites:
        findings.append(Finding(
            "ktiling", "<site-count>", name or "entry",
            f"found {len(sites)} pallas_call site(s): "
            f"{[s.name for s in sites]}",
            f"expected {expect_sites} pallas_call site(s) in the traced "
            f"graph, found {len(sites)} — the kernel lint is not seeing "
            "the kernels it claims to check"))
    findings += check_kernel_tiling(sites, name=name)
    findings += check_kernel_race(sites, name=name)
    findings += check_kernel_vmem(sites, max_bytes=vmem_budget, name=name)
    findings += check_kernel_precision(sites, name=name)
    findings += check_kernel_sentinel(sites, mask_inputs=mask_inputs,
                                      name=name)
    return findings


K_RULES = {
    "ktiling": check_kernel_tiling,
    "krace": check_kernel_race,
    "kvmem": check_kernel_vmem,
    "kprecision": check_kernel_precision,
    "ksentinel": check_kernel_sentinel,
}
