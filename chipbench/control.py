#!/usr/bin/env python3
"""Readings that set the limits of ``check.py``, at a cell's own size.

    python chipbench/control.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 3] [--out <readings.json>]

In one process: the program's first steps on every seed (one compiled
step, reused), then, once the program is freed, the plain reference on
each seed and, on the first ``--control-seeds`` seeds, the control (the
reference computed in fp8, one precision step below the configuration's
bfloat16, put in the program's place) and the planted fault
``half_batch`` (each worker's loss over half of its tokens, in the
reference put in the program's place).  Prints, per number, the largest
sound reading (lower) and the smallest control and fault readings.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent
for p in (CHECKOUT / "src", CHECKOUT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

VARIANTS = {"control_fp8": {"quant": "fp8"},
            "fault_half_batch": {"fault": "half_batch"}}



def program_readings(cell, seeds) -> dict:
    """{seed: first-step readings} of the program, one compile for all."""
    from chipbench.program import Program

    out = {}
    prog = Program(cell)
    prog.init_state(seeds[0])
    prog.compile()
    for s in seeds:
        prog.init_state(s)
        out[s] = prog.first_steps()
    prog.free()
    del prog
    gc.collect()
    return out


def reference_batches(cell, seed):
    import jax

    from chipbench.program import FIRST_STEPS
    from chipbench.reference import train as ref_train

    return [tuple(map(jax.device_get, ref_train.lm_stream(
        seed, t, cell.traffic, cell.config["vocab_size"])))
        for t in range(FIRST_STEPS)]


def as_program(reading: dict, batches) -> dict:
    """A reference reading in the place of the program's."""
    return {**reading, "batches": [{"tokens": t, "labels": lab}
                                   for t, lab in batches]}


def readings(cell, seeds, control_seeds, *, progs=None) -> dict:
    """Every number of ``check.numbers`` for the program and each variant,
    per seed."""
    from chipbench import check
    from chipbench.program import FIRST_STEPS
    from chipbench.reference import train as ref_train

    progs = progs if progs is not None else program_readings(cell, seeds)
    rows = {"program": {}, **{k: {} for k in VARIANTS}}
    for i, s in enumerate(seeds):
        ref = ref_train.run(cell.config, cell.traffic, s, FIRST_STEPS)
        batches = reference_batches(cell, s)
        rows["program"][s] = check.numbers(progs[s], ref, batches)
        if i < control_seeds:
            for name, kw in VARIANTS.items():
                got = ref_train.run(cell.config, cell.traffic, s,
                                    FIRST_STEPS, **kw)
                rows[name][s] = check.numbers(as_program(got, batches), ref,
                                              batches)
        print(json.dumps({"seed": s, **{k: v.get(s) for k, v in rows.items()}}),
              file=sys.stderr, flush=True)
    return rows


def summary(rows: dict) -> dict:
    names = next(iter(rows["program"].values())).keys()
    out = {}
    for n in names:
        out[n] = {"lower": max(r[n] for r in rows["program"].values())}
        for k in rows:
            if k != "program" and rows[k]:
                out[n][k] = min(r[n] for r in rows[k].values())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    from chipbench import spec
    from chipbench.run import NO_CHIP, chips_ok, configure_cache

    cell = spec.load_cell(args.workload)
    why = chips_ok(cell, jax.devices())
    if why:
        print(f"chipbench: {why}", file=sys.stderr)
        return NO_CHIP
    configure_cache()
    rows = readings(cell, args.seeds, args.control_seeds)
    result = {"workload": cell.name, "rows": {k: {str(s): v for s, v in r.items()}
                                              for k, r in rows.items()},
              "summary": summary(rows)}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
