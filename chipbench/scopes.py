"""Device time of the train step's stages, from the program's named scopes.

The program runs each stage of its step under a ``jax.named_scope``
(``docs/architecture.md``, "Stage scopes"); XLA keeps the scope path as
the ``op_name`` metadata of every instruction, and the profiler names each
op event by its instruction.  So the step's op events can be put back
into stages:

- only op events that start inside an execution of the step module are
  kept (other modules reuse names like ``fusion.1``);
- each op gets its *self time*: the part of its clipped interval that no
  op started later covers (a ``while`` holds its body's ops), so the self
  times of all the step's ops sum to the union of their intervals, the
  step module's busy time, by construction;
- each op is classified by its scope path (:func:`classify`);
- the self times are summed per class and per step, then averaged over
  the cell's devices.

The scope map comes from the compiled step's HLO text, through the
program's own parser (``repro.analysis.hlo.attributed_scopes``: the
``op_name`` of each instruction, and for the instructions XLA made
without one, such as layout copies and the loops a big reshape becomes,
the path of the nearest instruction they feed).  The harness does not
hand the readers that text, so :func:`step_scopes` compiles the cell's
step again, as ``run.py`` does; through the persistent compile cache that
is a load, not a compile.  A program whose parser has no
``attributed_scopes`` (one older than the scopes) gives an empty map, and
every reader of a scope then returns 0.0.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass

STAGES = ("grad", "attack", "aggregate", "optimizer", "telemetry")
PARTS = ("embed", "attention", "mlp", "lm_head")
AGGREGATE = ("codec", "pack", "gram", "solve", "combine", "coord_stats")

_TOKEN = re.compile(r"[\w.\-]+")


@dataclass(frozen=True)
class OpClass:
    stage: str | None        # first of STAGES in the path; None: unscoped
    part: str | None         # innermost of PARTS
    direction: str | None    # inside ``grad``: fwd, bwd or remat
    sub: str | None          # inside ``aggregate``: innermost of AGGREGATE


def classify(path: str) -> OpClass:
    """The class of one op from its ``op_name`` path.

    Recomputation is tested before the backward pass: JAX runs the
    rematerialized forward inside the transpose, so its path holds both
    ``transpose(`` and ``rematted_computation``.
    """
    names = _TOKEN.findall(path)
    stage = next((n for n in names if n in STAGES), None)
    part = next((n for n in reversed(names) if n in PARTS), None)
    direction = sub = None
    if stage == "grad":
        direction = ("remat" if "rematted_computation" in names
                     else "bwd" if "transpose(" in path else "fwd")
    if stage == "aggregate":
        sub = next((n for n in reversed(names) if n in AGGREGATE), None)
    return OpClass(stage, part, direction, sub)


def self_times(events) -> list:
    """Self time of each ``(start, end)`` interval: at every instant the
    time goes to the interval that started last among those running (ties
    of start: the shorter one), so the self times sum to the union."""
    out = [0] * len(events)
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    stack, now = [], None

    def advance(t):
        nonlocal now
        while stack and now < t:
            top = stack[-1]
            upto = min(t, events[top][1])
            if upto > now:
                out[top] += upto - now
                now = upto
            if events[top][1] <= now:
                stack.pop()
        now = t if now is None else max(now, t)

    for i in order:
        advance(events[i][0])
        stack.append(i)
    if stack:
        advance(max(e for _, e in events))
    return out


def _inside(starts, ends, t) -> bool:
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < ends[i]


def step_table(trace, step_module: str, scopes: dict) -> tuple:
    """({OpClass: seconds}, busy seconds), each the mean over the trace's
    devices, of the ops inside the step module's executions."""
    table, busy, classes = {}, 0.0, {}
    for dev in trace.devices:
        mods = sorted((s, e) for name, s, e in trace._clip(dev.modules)
                      if name.split("(")[0] == step_module)
        starts, ends = [s for s, _ in mods], [e for _, e in mods]
        ops = [(name, s, e) for name, s, e in trace._clip(dev.ops)
               if _inside(starts, ends, s)]
        own = self_times([(s, e) for _, s, e in ops])
        for (name, _, _), t in zip(ops, own):
            if name not in classes:
                classes[name] = classify(scopes.get(name, ""))
            c = classes[name]
            table[c] = table.get(c, 0.0) + t * 1e-9
        busy += sum(own) * 1e-9
    n = len(trace.devices)
    return {c: t / n for c, t in table.items()}, busy / n


def other_module_seconds(trace, step_module: str) -> float:
    """Device time of every module other than the step, mean over the
    trace's devices."""
    total = sum(e - s for dev in trace.devices
                for name, s, e in trace._clip(dev.modules)
                if name.split("(")[0] != step_module)
    return total * 1e-9 / len(trace.devices)


def step_scopes(cell) -> dict:
    """``{instruction: scope path}`` of the cell's compiled step, or {}
    where the program's HLO parser has no ``attributed_scopes``."""
    from repro.analysis import hlo

    parse = getattr(hlo, "attributed_scopes", None)
    if parse is None:
        return {}
    from chipbench.program import Program

    prog = Program(cell)
    prog.init_state(0)        # the compiled step does not depend on the seed
    try:
        return parse(prog.compile().as_text())
    finally:
        prog.free()


def _table(ctx) -> dict:
    """The context's stage table, made once: {} where the program names
    no scopes."""
    table = getattr(ctx, "_stage_table", None)
    if table is None:
        from chipbench.spec import Cell

        cell = Cell(name="scopes", chips=ctx.chips, config=ctx.config,
                    traffic=ctx.traffic, limits={}, per_layer=[])
        scopes = step_scopes(cell)
        table = (step_table(ctx.trace, ctx.step_module, scopes)[0]
                 if scopes else {})
        ctx._stage_table = table
    return table


def ms(ctx, **match) -> float:
    """Device ms per step of the step's ops whose class has every field
    given (``stage=None`` selects the unscoped ops); 0.0 where the program
    names no scopes."""
    secs = sum(t for c, t in _table(ctx).items()
               if all(getattr(c, k) == v for k, v in match.items()))
    return secs / ctx.steps * 1e3
