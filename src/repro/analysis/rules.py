"""The rule registry: static-analysis passes over jaxprs and compiled HLO.

Each rule is a function ``(Graph | domain object, **params) -> list[Finding]``
registered under a family id.  The families (see docs/static_analysis.md
for the catalog):

``shape``
    No tensor dimension may exceed a declared bound (``max_dim`` — the
    rank-p solver's no-dim-beyond-p invariant), and declared dimensions
    must be absent (``forbidden_dims`` — per-device full-coordinate
    widths under a mesh) / present (``require_dims`` — detector sanity:
    the per-shard widths must actually show up).
``precision``
    ``dot_general`` (and sum-accumulating ops: ``reduce_sum``,
    ``scatter-add``, ``cumsum``, convolutions) whose operands are
    bf16/fp16 must accumulate in >= fp32 (``preferred_element_type`` on
    dots; an upcast before the reduce otherwise) — detected as a
    low-precision *output* of a low-precision contraction, the exact bug
    class ``tree_combine`` and the sketch rescale fixed by hand.
``transfer``
    No host callbacks or device transfers inside a jitted hot path.
``mask``
    The membership mask must be consumed as a *traced* operand — a
    Python branch on it (concretization) or silently ignoring it are
    both findings.
``collectives``
    Per-device collective byte volume (trip-count-corrected, via
    :mod:`repro.analysis.hlo`) must stay under a declared budget.

``recompile`` is the sixth family; being a runtime property it lives in
:mod:`repro.analysis.recompile` (the registry lists it for the catalog).

Jaxpr-level rules recurse into every sub-jaxpr (pjit bodies, scan/while
bodies, custom-vjp branches), so a rule sees through ``jax.jit`` wrappers
and control flow.  HLO-level rules see the compiled, SPMD-partitioned
module — shapes there are per-device, which is what makes the
no-full-width check meaningful.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.extend import core as jax_core

from repro.analysis.findings import Finding
from repro.analysis.hlo import (COMP_HEADER_RE, DTYPE_BYTES, SHAPE_RE,
                                parse_collectives)

__all__ = ["Graph", "capture", "check_shape", "check_precision",
           "check_transfer", "check_mask", "check_collectives",
           "full_width_dims", "RULES"]


# ---------------------------------------------------------------------------
# capture: one entry point -> (jaxpr, compiled HLO)
# ---------------------------------------------------------------------------

@dataclass
class Graph:
    """One traced/compiled entry point, ready for the rules.

    ``jaxpr`` is the closed jaxpr of a no-argument thunk (inputs appear
    as constvars — the rules only walk equations, so that is immaterial);
    ``hlo`` is the compiled post-SPMD-partition HLO text, or ``None``
    when only trace-level rules are wanted.
    """

    name: str
    jaxpr: jax_core.ClosedJaxpr | None = None
    hlo: str | None = None


def capture(fn, *args, name: str | None = None, compile: bool = True,
            **kwargs) -> Graph:
    """Trace (and optionally compile) ``fn(*args, **kwargs)`` for analysis.

    Non-array arguments (configs, meshes, strings) are closed over, so
    any signature works.  For entry points that need explicit input
    shardings, build the :class:`Graph` by hand from
    ``jit(...).lower(specs).compile().as_text()`` instead.
    """
    thunk = lambda: fn(*args, **kwargs)
    closed = jax.make_jaxpr(thunk)()
    hlo = None
    if compile:
        hlo = jax.jit(thunk).lower().compile().as_text()
    return Graph(name or getattr(fn, "__name__", "entry"), closed, hlo)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(params: dict):
    for v in params.values():
        if isinstance(v, jax_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for w in v:
                if isinstance(w, jax_core.ClosedJaxpr):
                    yield w.jaxpr
                elif isinstance(w, jax_core.Jaxpr):
                    yield w


def iter_eqns(jaxpr: jax_core.Jaxpr, scope: str = "entry"):
    """Yield ``(eqn, scope)`` over the jaxpr and every nested sub-jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn, scope
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, f"{scope}/{eqn.primitive.name}")


def _shaped(aval):
    return getattr(aval, "shape", None) is not None and hasattr(aval, "dtype")


# ---------------------------------------------------------------------------
# SHAPE
# ---------------------------------------------------------------------------

def _hlo_typed_lines(hlo_text: str):
    """Yield ``(computation, line, dims_in_line)`` for every HLO op line."""
    comp = "<preamble>"
    for raw in hlo_text.splitlines():
        line = raw.strip()
        hm = COMP_HEADER_RE.match(line)
        if hm:
            comp = hm.group(1)
            continue
        dims = []
        for dt, ds in SHAPE_RE.findall(line):
            if dt not in DTYPE_BYTES:
                continue
            dims += [int(d) for d in ds.split(",") if d]
        if dims:
            yield comp, line, dims


def check_shape(graph: Graph, *, max_dim: int | None = None,
                forbidden_dims=(), require_dims=()) -> list[Finding]:
    """SHAPE: bound / forbid / require tensor dimensions.

    Prefers the compiled HLO when present (per-device, post-partition
    shapes — the only level where the no-full-width invariant means
    anything); falls back to jaxpr avals otherwise (enough for
    ``max_dim``-style blow-up checks, and cheap — no compile).
    """
    forbidden = set(forbidden_dims)
    required = set(require_dims)
    findings: list[Finding] = []
    seen: set[int] = set()

    def offending(dims):
        bad = [d for d in dims if max_dim is not None and d > max_dim]
        bad += [d for d in dims if d in forbidden]
        return bad

    if graph.hlo is not None:
        for comp, line, dims in _hlo_typed_lines(graph.hlo):
            seen.update(dims)
            bad = offending(dims)
            if bad:
                op = line.split("=", 1)[-1].strip().split("(", 1)[0]
                op = op.split()[-1] if op.split() else "?"
                findings.append(Finding(
                    "shape", op, comp, line,
                    f"tensor dimension(s) {sorted(set(bad))} violate the "
                    f"shape contract (max_dim={max_dim}, "
                    f"forbidden={sorted(forbidden)})"))
    elif graph.jaxpr is not None:
        for eqn, scope in iter_eqns(graph.jaxpr.jaxpr):
            avals = [v.aval for v in list(eqn.outvars) + list(eqn.invars)
                     if hasattr(v, "aval") and _shaped(v.aval)]
            dims = [int(d) for a in avals for d in a.shape]
            seen.update(dims)
            bad = offending(dims)
            if bad:
                findings.append(Finding(
                    "shape", eqn.primitive.name, scope, str(eqn),
                    f"tensor dimension(s) {sorted(set(bad))} violate the "
                    f"shape contract (max_dim={max_dim}, "
                    f"forbidden={sorted(forbidden)})"))
    else:
        raise ValueError("check_shape: graph has neither jaxpr nor HLO")

    if required and not (required & seen):
        findings.append(Finding(
            "shape", "<absent>", graph.name, f"dims seen: {sorted(seen)[:20]}",
            f"none of the required dimensions {sorted(required)} appear — "
            "the detector is not looking at the graph it thinks it is"))
    return findings


def full_width_dims(tree, n_shards: int) -> tuple[set[int], set[int]]:
    """(forbidden, required) dims for the no-full-width-per-device check.

    For a worker-major pytree sharded ``n_shards`` ways over the
    coordinate axis: the full flat width of every cleanly-divisible leaf
    (and its leading coordinate dim), plus the concatenated total when
    every leaf divides, must be *absent* from per-device HLO; at least
    one per-shard width must be *present* (detector sanity).  Leaves
    whose width does not divide ``n_shards`` are excluded — padding makes
    their per-device shapes implementation-defined.
    """
    leaves = jax.tree.leaves(tree)
    forbidden: set[int] = set()
    required: set[int] = set()
    total, all_divide = 0, True
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) < 2:
            continue
        flat = math.prod(shape[1:])
        total += flat
        if flat % n_shards == 0 and flat // n_shards > 1:
            forbidden.add(flat)
            required.add(flat // n_shards)
            if shape[1] != flat and shape[1] % n_shards == 0 \
                    and shape[1] // n_shards > 1:
                forbidden.add(shape[1])
                required.add(shape[1] // n_shards)
        else:
            all_divide = False
    if all_divide and total and total % n_shards == 0:
        forbidden.add(total)
    return forbidden - required, required


# ---------------------------------------------------------------------------
# PRECISION
# ---------------------------------------------------------------------------

_LOW = (jnp.bfloat16, jnp.float16)
# ops that *accumulate* a sum: a low-precision accumulator here loses mass
_ACCUM_PRIMS = {"dot_general", "reduce_sum", "cumsum", "scatter-add",
                "conv_general_dilated"}
_HLO_DOT_RE = re.compile(r"=\s*(bf16|f16)\[[\d,]*\][^=]*\b(dot|convolution)\(")


def _is_low(dtype) -> bool:
    return any(dtype == jnp.dtype(d) for d in _LOW)


def check_precision(graph: Graph) -> list[Finding]:
    """PRECISION: low-precision inputs must accumulate in >= fp32.

    A ``dot_general`` / reduction whose operands are bf16/fp16 *and*
    whose output is bf16/fp16 accumulated in low precision — the fix is
    ``preferred_element_type=jnp.float32`` (dots) or an fp32 upcast
    before the reduce, casting only the result back down.
    """
    findings: list[Finding] = []
    if graph.jaxpr is not None:
        for eqn, scope in iter_eqns(graph.jaxpr.jaxpr):
            if eqn.primitive.name not in _ACCUM_PRIMS:
                continue
            in_dtypes = [v.aval.dtype for v in eqn.invars
                         if hasattr(v, "aval") and _shaped(v.aval)]
            out_dtypes = [v.aval.dtype for v in eqn.outvars
                          if _shaped(v.aval)]
            if any(_is_low(d) for d in in_dtypes) \
                    and all(_is_low(d) for d in out_dtypes) and out_dtypes:
                findings.append(Finding(
                    "precision", eqn.primitive.name, scope, str(eqn),
                    f"{eqn.primitive.name} on "
                    f"{'/'.join(str(d) for d in in_dtypes)} inputs "
                    "accumulates in low precision — use "
                    "preferred_element_type=jnp.float32 (dots) or upcast "
                    "before the reduction"))
    elif graph.hlo is not None:
        comp = "<preamble>"
        for raw in graph.hlo.splitlines():
            line = raw.strip()
            hm = COMP_HEADER_RE.match(line)
            if hm:
                comp = hm.group(1)
                continue
            m = _HLO_DOT_RE.search(line)
            if m:
                findings.append(Finding(
                    "precision", m.group(2), comp, line,
                    f"{m.group(2)} emits a {m.group(1)} result — the "
                    "contraction accumulates in low precision"))
    else:
        raise ValueError("check_precision: graph has neither jaxpr nor HLO")
    return findings


# ---------------------------------------------------------------------------
# TRANSFER
# ---------------------------------------------------------------------------

_TRANSFER_PRIMS = {"infeed", "outfeed", "copy_to_host_async"}


def _is_real_device_put(eqn) -> bool:
    # jnp ops insert no-op device_put[devices=[None]] around Python
    # literals; only an explicit target device is a transfer.
    return eqn.primitive.name == "device_put" and any(
        d is not None for d in eqn.params.get("devices", []))
_HLO_TRANSFER_OPS = {"send", "recv", "send-done", "recv-done", "infeed",
                     "outfeed"}
_HLO_CALLBACK_RE = re.compile(
    r'custom[-_]call\(.*custom_call_target="([^"]*(?:callback|host)[^"]*)"',
    re.IGNORECASE)


def check_transfer(graph: Graph) -> list[Finding]:
    """TRANSFER: no host callbacks / device transfers in a jitted hot path.

    Jaxpr level: callback primitives (``pure_callback``, ``io_callback``,
    ``debug_callback``, ...), infeed/outfeed, and ``device_put`` with an
    explicit target device (the no-op ``devices=[None]`` form jnp wraps
    Python literals in is ignored).  HLO level: send/recv/infeed/outfeed
    ops and custom-calls into the Python callback runtime.
    """
    findings: list[Finding] = []
    if graph.jaxpr is not None:
        for eqn, scope in iter_eqns(graph.jaxpr.jaxpr):
            name = eqn.primitive.name
            if "callback" in name or name in _TRANSFER_PRIMS \
                    or _is_real_device_put(eqn):
                findings.append(Finding(
                    "transfer", name, scope, str(eqn),
                    f"host transfer / callback primitive {name!r} inside "
                    "the jitted hot path — the step would synchronize "
                    "with the host every call"))
    if graph.hlo is not None:
        comp = "<preamble>"
        for raw in graph.hlo.splitlines():
            line = raw.strip()
            hm = COMP_HEADER_RE.match(line)
            if hm:
                comp = hm.group(1)
                continue
            m = re.search(r"=\s*[^=]*?\b([\w\-]+)\(", line)
            op = m.group(1) if m else ""
            if op in _HLO_TRANSFER_OPS:
                findings.append(Finding(
                    "transfer", op, comp, line,
                    f"HLO {op} — host/device transfer compiled into the "
                    "hot path"))
            cb = _HLO_CALLBACK_RE.search(line)
            if cb:
                findings.append(Finding(
                    "transfer", "custom-call", comp, line,
                    f"host callback custom-call {cb.group(1)!r} compiled "
                    "into the hot path"))
    return findings


# ---------------------------------------------------------------------------
# MASK
# ---------------------------------------------------------------------------

def check_mask(fn, mask, *, name: str = "entry") -> list[Finding]:
    """MASK: membership-mask discipline for ``fn(mask)``.

    ``fn`` must take the ``(W,)`` mask as its only argument (close over
    everything else).  Two findings are possible: the mask is consumed as
    a Python value (a branch forced concretization — membership changes
    would recompile or crash under jit), or the traced mask is ignored
    entirely (the "masked" path silently aggregates absent workers).
    """
    try:
        closed = jax.make_jaxpr(fn)(mask)
    except (jax.errors.TracerBoolConversionError,
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError) as e:
        return [Finding(
            "mask", "python-branch", name, str(e).splitlines()[0],
            "membership mask is consumed as a Python value — it must stay "
            "a traced operand so membership changes never recompile")]
    mask_vars = set(closed.jaxpr.invars)

    def used(jaxpr) -> bool:
        for eqn in jaxpr.eqns:
            if mask_vars & set(v for v in eqn.invars
                               if isinstance(v, jax_core.Var)):
                return True
        return False

    if not used(closed.jaxpr):
        return [Finding(
            "mask", "<unused>", name, f"invars: {closed.jaxpr.invars}",
            "membership mask is accepted but never consumed — absent "
            "workers would silently participate in the aggregate")]
    return []


# ---------------------------------------------------------------------------
# COLLECTIVES
# ---------------------------------------------------------------------------

def check_collectives(graph: Graph, total_devices: int, *,
                      max_bytes_per_device: float) -> list[Finding]:
    """COLLECTIVES: per-device collective byte budget.

    Uses the trip-count-corrected parser (:func:`repro.analysis.hlo.
    parse_collectives`) — scanned-layer graphs account their loops.
    """
    if graph.hlo is None:
        raise ValueError("check_collectives needs compiled HLO "
                         "(collectives only exist post-partitioning)")
    stats = parse_collectives(graph.hlo, total_devices)
    if stats.total_moved_bytes > max_bytes_per_device:
        return [Finding(
            "collectives", "total", graph.name, stats.summary(),
            f"per-device collective volume {stats.total_moved_bytes:.3e} B "
            f"exceeds the budget {max_bytes_per_device:.3e} B")]
    return []


# ---------------------------------------------------------------------------
# registry (the catalog the CLI and docs enumerate)
# ---------------------------------------------------------------------------

RULES = {
    "shape": check_shape,
    "precision": check_precision,
    "transfer": check_transfer,
    "mask": check_mask,
    "collectives": check_collectives,
    # runtime family — see repro.analysis.recompile
    "recompile": None,
}


def _register_kernel_rules():
    # Deferred: pallas_rules imports this module (iter_eqns, Graph).
    from repro.analysis import pallas_rules as _pk

    RULES.update(_pk.K_RULES)


_register_kernel_rules()
