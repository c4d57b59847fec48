"""The comparison that decides ``correct``.

What the timed path produced in its first steps (the batches it fed, each
step's loss, the first aggregated gradient as the optimizer got it, the
parameters' change after the last of them) against the plain reference
run from the same seed.  Each number has a limit of its own, in
``limits/<cell>.json``; how each limit was set is in PERF.md.

- ``tokens_mismatch``: tokens and labels fed that differ from the
  reference stream (exact: limit 0).
- ``loss_gap``: the largest relative gap of a step's loss.
- ``loss_gap_01``: the same over steps 0 and 1 only, the steps taken on
  the initial weights (the schedule's first learning rate is 0), for a
  cell whose later steps inherit a sensitive aggregation (PERF.md).

A cell compares the numbers that its limits file names.
- ``grad_gap`` / ``change_gap``: by the worst leaf, the gap between the
  program's norm and the reference's, over the larger of the reference's
  norm of that leaf and of the median leaf.  Leaves whose reference
  gradient is under a thousandth of the median leaf's are left out: Adam
  moves them by round-off alone.
"""

from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE = 1e-3    # of the median leaf's first-gradient norm


def _leaf_gap(prog, ref, keep) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape or not np.all(np.isfinite(prog)):
        return math.inf
    floor = np.median(ref[keep])
    gaps = np.abs(prog - ref)[keep] / np.maximum(ref[keep], floor)
    return float(np.max(gaps))


def numbers(prog: dict, ref: dict, ref_batches: list) -> dict:
    """The compared numbers of one run."""
    mismatch = 0
    for fed, (tokens, labels) in zip(prog["batches"], ref_batches):
        mismatch += int(np.sum(np.asarray(fed["tokens"]) != tokens))
        mismatch += int(np.sum(np.asarray(fed["labels"]) != labels))
    gaps = [(abs(a - b) / abs(b) if math.isfinite(a) else math.inf)
            for a, b in zip(prog["losses"], ref["losses"])]
    ref_g = np.asarray(ref["first_grad"], np.float64)
    keep = ref_g >= NEGLIGIBLE * np.median(ref_g)
    return {"tokens_mismatch": mismatch,
            "loss_gap": max(gaps),
            "loss_gap_01": max(gaps[:2]),
            "grad_gap": _leaf_gap(prog["first_grad"], ref_g, keep),
            "change_gap": _leaf_gap(prog["change"], ref["change"], keep)}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a number without a finite
    value fails."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in checks.values())
    return ok, checks


def report_lines(checks: dict) -> list[str]:
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r}) -> "
            f"{'ok' if v['value'] <= v['limit'] else 'FAILED'}"
            for k, v in checks.items()]
