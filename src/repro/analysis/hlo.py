"""Parse compiled (SPMD-partitioned) HLO text: collectives, costs, scopes.

``cost_analysis()`` does not report collective traffic — and it counts
``while`` bodies once — so the roofline's collective term comes from here:

1. the HLO text is split into computations;
2. every all-gather / all-reduce / reduce-scatter / all-to-all /
   collective-permute op's *per-device* byte volume is derived from the
   op's output shape (post-partition HLO shapes are per-device) and its
   replica-group size with the standard ring multipliers:

       all-gather          out_bytes * (g-1)/g      (bytes received)
       all-reduce          out_bytes * 2(g-1)/g     (reduce-scatter + gather)
       reduce-scatter      out_bytes * (g-1)
       all-to-all          out_bytes * (g-1)/g
       collective-permute  out_bytes

3. ``while`` ops carry ``backend_config={"known_trip_count":{"n":...}}``
   after XLA's loop analysis; each computation's collectives are multiplied
   by the product of enclosing-loop trip counts (nested scans compose), so
   scanned-layer models report the same collective volume as unrolled ones
   (validated in tests/test_hlo_stats.py and against an unrolled dry-run).

:func:`op_scopes` maps every instruction to its ``jax.named_scope`` path,
and :func:`attributed_scopes` also gives one to the instructions XLA made
without any; that is how a profiler's op events (named by instruction) are
put back into the train step's stages (docs/architecture.md, "Stage
scopes").
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_RE = re.compile(
    r"=\s*(\([^)]*\)|[\w\[\],{}]+)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(")
SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
COMP_HEADER_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{")
WHILE_RE = re.compile(r"\bwhile\(.*?body=%?([\w.\-]+)")
TRIP_RE = re.compile(r"known_trip_count\D+(\d+)")


def shape_dims(hlo_text: str) -> set[int]:
    """Every array dimension appearing in any typed shape of the HLO text.

    Used to assert *absence* of blow-up intermediates: e.g. the rank-p FA
    solver at p=32 must never materialize an array with a q-sized
    dimension (q = p + p(p-1)/2 = 528) — see tests/test_gram_solvers.py.
    """
    dims: set[int] = set()
    for dt, ds in SHAPE_RE.findall(hlo_text):
        if dt not in DTYPE_BYTES:
            continue
        for d in ds.split(","):
            if d:
                dims.add(int(d))
    return dims


def _shape_bytes(shape_text: str, last_only: bool = False) -> int:
    shapes = SHAPE_RE.findall(shape_text)
    if not shapes:
        return 0
    if last_only:
        shapes = shapes[-1:]
    total = 0
    for dt, dims in shapes:
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = GROUPS_EXPL_RE.search(line)
    if m:
        return len([t for t in m.group(1).split(",") if t.strip() != ""])
    return default


def _moved_bytes(kind: str, out_bytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    return {
        "all-gather": out_bytes * (g - 1) / g,
        "all-reduce": out_bytes * 2 * (g - 1) / g,
        "reduce-scatter": out_bytes * (g - 1),
        "all-to-all": out_bytes * (g - 1) / g,
        "collective-permute": float(out_bytes),
    }.get(kind, 0.0)


OP_LINE_RE = re.compile(
    r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[\w\[\],{}]+)\s+"
    r"([\w\-]+)\(([^)]*)\)")
OPERAND_RE = re.compile(r"%([\w.\-]+)")
CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _dims(shape_text: str) -> list[int]:
    m = SHAPE_RE.search(shape_text)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclass
class HloCost:
    flops: float            # loop-corrected dot FLOPs, per device
    hbm_bytes: float        # loop-corrected op-boundary bytes, per device
    raw_flops: float        # uncorrected (for comparison with cost_analysis)


def parse_cost(hlo_text: str) -> HloCost:
    """Loop-corrected FLOPs + HBM-traffic estimate from partitioned HLO.

    XLA's HloCostAnalysis counts while bodies once; this walks the
    computation graph with trip-count multipliers instead.  FLOPs counts
    ``dot`` ops (2 * prod(out) * prod(contracted lhs dims)) anywhere they
    appear; HBM bytes counts operand+output bytes of ops in *control*
    computations only (entry, while bodies, branches) — ops inside fusion
    computations don't touch HBM, the fusion call-site does.
    """
    comp = "<preamble>"
    shapes: dict[str, str] = {}
    comp_ops: dict = defaultdict(list)   # comp -> [(name, shape, op, opnds, line)]
    while_edges: list = []
    call_edges: list = []                # (parent, callee) for fusion/call
    fusion_comps: set = set()
    entry = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        hm = COMP_HEADER_RE.match(line)
        if hm:
            comp = hm.group(1)
            if raw.startswith("ENTRY") or line.startswith("ENTRY"):
                entry = comp
            continue
        om = OP_LINE_RE.match(line)
        if not om:
            continue
        name, shape_text, op, operands = om.groups()
        shapes[name] = shape_text
        comp_ops[comp].append((name, shape_text, op, operands, line))
        if op == "while":
            wm = WHILE_RE.search(line)
            tm = TRIP_RE.search(line)
            if wm:
                while_edges.append((comp, wm.group(1),
                                    int(tm.group(1)) if tm else 1))
        cm = CALLS_RE.search(line)
        if cm and op in ("fusion", "call", "custom-call", "reduce", "map",
                         "sort", "scatter", "select-and-scatter"):
            call_edges.append((comp, cm.group(1)))
            if op == "fusion":
                fusion_comps.add(cm.group(1))

    mult: dict = defaultdict(lambda: 0.0)
    mult[entry or "<preamble>"] = 1.0
    for _ in range(32):
        changed = False
        for parent, body, trips in while_edges:
            new = mult[parent] * trips
            if new > mult.get(body, 0.0):
                mult[body] = new
                changed = True
        for parent, callee in call_edges:
            new = mult[parent]
            if new > mult.get(callee, 0.0):
                mult[callee] = new
                changed = True
        if not changed:
            break
    # computations that were never reached (e.g. cond computations) get 1x
    flops = raw_flops = hbm = 0.0
    for comp_name, ops in comp_ops.items():
        m = mult.get(comp_name, 1.0) or 1.0
        in_fusion = comp_name in fusion_comps
        for name, shape_text, op, operands, line in ops:
            if op == "dot":
                out_n = 1
                for d in _dims(shape_text):
                    out_n *= d
                contract = 1
                cm2 = CONTRACT_RE.search(line)
                opnd_names = OPERAND_RE.findall(operands)
                if cm2 and opnd_names:
                    lhs_dims = _dims(shapes.get(opnd_names[0], ""))
                    for idx in cm2.group(1).split(","):
                        if idx and int(idx) < len(lhs_dims):
                            contract *= lhs_dims[int(idx)]
                f = 2.0 * out_n * contract
                flops += f * m
                raw_flops += f
            if not in_fusion and op not in ("parameter", "constant",
                                            "get-tuple-element", "tuple",
                                            "bitcast"):
                b = _shape_bytes(shape_text)
                for opn in OPERAND_RE.findall(operands):
                    if opn in shapes:
                        b += _shape_bytes(shapes[opn])
                hbm += b * m
    return HloCost(flops=flops, hbm_bytes=hbm, raw_flops=raw_flops)


@dataclass
class CollectiveStats:
    per_kind_bytes: dict
    per_kind_count: dict
    total_moved_bytes: float                    # per device, loop-corrected
    loop_multipliers: dict = field(default_factory=dict)

    def summary(self) -> str:
        parts = [f"{k}: n={self.per_kind_count[k]} "
                 f"moved={self.per_kind_bytes[k]/1e6:.1f}MB"
                 for k in sorted(self.per_kind_bytes)]
        return "; ".join(parts) or "none"


def parse_collectives(hlo_text: str, total_devices: int) -> CollectiveStats:
    # --- pass 1: split into computations; record whiles + trip counts ---
    comp = "<preamble>"
    per_comp_ops: dict = defaultdict(list)      # comp -> [(kind, moved, n)]
    while_edges: list = []                      # (parent_comp, body, trips)
    entry = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        hm = COMP_HEADER_RE.match(line)
        if hm:
            comp = hm.group(1)
            if raw.startswith("ENTRY") or line.startswith("ENTRY"):
                entry = comp
            continue
        wm = WHILE_RE.search(line)
        if wm:
            tm = TRIP_RE.search(line)
            trips = int(tm.group(1)) if tm else 1
            while_edges.append((comp, wm.group(1), trips))
        cm = COLLECTIVE_RE.search(line)
        if cm:
            shape_text, op = cm.group(1), cm.group(2)
            if op.endswith("-start"):
                op = op[:-6]
            # async -start ops have tuple (operand, result) shapes: use result
            last_only = shape_text.startswith("(")
            out_bytes = _shape_bytes(shape_text, last_only=last_only)
            g = _group_size(line, total_devices)
            per_comp_ops[comp].append((op, _moved_bytes(op, out_bytes, g)))

    # --- pass 2: propagate loop multipliers through the while-call graph ---
    mult: dict = defaultdict(lambda: 1.0)
    if entry:
        mult[entry] = 1.0
    # iterate to fixpoint (nesting depth is tiny)
    for _ in range(16):
        changed = False
        for parent, body, trips in while_edges:
            new = mult[parent] * trips
            if mult.get(body) != new:
                mult[body] = new
                changed = True
        if not changed:
            break

    per_bytes: dict = defaultdict(float)
    per_count: dict = defaultdict(int)
    for comp_name, ops in per_comp_ops.items():
        m = mult.get(comp_name, 1.0)
        for op, moved in ops:
            per_bytes[op] += moved * m
            per_count[op] += int(m) if m > 1 else 1
    return CollectiveStats(dict(per_bytes), dict(per_count),
                           sum(per_bytes.values()),
                           {b: t for _, b, t in while_edges})


OP_NAME_RE = re.compile(r'^(?:ROOT\s+)?%?([\w.\-]+)\s*=.*\bmetadata=\{[^}]*'
                        r'\bop_name="([^"]*)"')


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: op_name path}`` of an optimized HLO module.

    Every instruction that carries ``metadata={op_name=...}`` is listed,
    in every computation: entry, ``while`` bodies and conditions, fusions.
    Instruction names are unique within a module, and they are the names a
    profiler gives the module's op events.  The path is JAX's name stack,
    e.g. ``jit(step)/grad/vmap(transpose(jvp()))/while/body/.../mlp/dot``.
    """
    out = {}
    for line in hlo_text.splitlines():
        m = OP_NAME_RE.match(line.strip())
        if m:
            out[m.group(1)] = m.group(2)
    return out


INSTR_RE = re.compile(r"^(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")


def attributed_scopes(hlo_text: str) -> dict[str, str]:
    """:func:`op_scopes`, with a path for the instructions that have none.

    XLA's own passes make instructions without ``op_name``: layout copies,
    the loops and ``dynamic-update-slice`` chains it rewrites a big
    ``concatenate`` or ``reshape`` into, async copy halves.  Each such
    instruction takes the path of the nearest instruction it feeds that
    has one (breadth first, in operand order), else of the nearest one
    that feeds it, else that of the instruction that calls its computation
    (the ``while`` that runs a loop body).  Only paths from metadata are
    searched, so the result does not depend on the order of the visits.
    """
    named = op_scopes(hlo_text)
    comp_of, refs, comp = {}, {}, None
    for line in hlo_text.splitlines():
        line = line.strip()
        m = COMP_HEADER_RE.match(line)
        if m:
            comp = m.group(1)
            continue
        m = INSTR_RE.match(line)
        if m and comp is not None:
            comp_of[m.group(1)] = comp
            refs[m.group(1)] = OPERAND_RE.findall(m.group(2))
    operands, users, caller = {}, defaultdict(list), {}
    for name, tokens in refs.items():
        operands[name] = [t for t in tokens if t in comp_of]
        for t in operands[name]:
            users[t].append(name)
        for t in tokens:
            if t not in comp_of:                # a computation it calls
                caller.setdefault(t, name)

    def nearest(start, edges):
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for x in frontier:
                for y in edges.get(x, ()):
                    if y in named:
                        return named[y]
                    if y not in seen:
                        seen.add(y)
                        nxt.append(y)
            frontier = nxt
        return None

    out = dict(named)
    todo = [n for n in refs if n not in named]
    while todo:                  # a loop body waits for its caller's path
        left = []
        for name in todo:
            path = (nearest(name, users) or nearest(name, operands)
                    or out.get(caller.get(comp_of[name])))
            if path is None:
                left.append(name)
            else:
                out[name] = path
        if len(left) == len(todo):
            break
        todo = left
    return out
