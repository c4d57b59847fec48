#!/usr/bin/env python3
"""Records the train-step trace and HLO that ``test_scopes.py`` reads.

    python chipbench/tests/record_step_trace.py <out dir>

On a TPU: the program's real train step (``build_train_step`` through
``program.Program``) at ``tiny.py``'s sizes, FA, W=4, with the Pallas
kernels on the chip, run through the benchmark's own window for 2 steps
(one log block, ``log_every`` 2) inside ``chipbench.window``.  Writes
the trace as ``step.xplane.pb.gz``, the compiled step's HLO text as
``step.hlo.txt.gz`` and the median rule's compiled step as
``step_median.hlo.txt.gz`` (for its ``coord_stats_pallas`` kernels) into
the directory given, all gzipped: plain, they hold 3.9 MB and 1.2 MB each.
"""

import glob
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
for p in (CHECKOUT / "src", CHECKOUT):
    sys.path.insert(0, str(p))

STEPS = 2


def tiny(workload):
    from chipbench.tests import tiny as tiny_cell

    cell = tiny_cell.cell(workload)
    cell.traffic["log_every"] = STEPS
    return cell


def write_gz(path: Path, data: bytes) -> None:
    with open(path, "wb") as f, gzip.GzipFile(fileobj=f, mode="wb",
                                              mtime=0) as z:
        z.write(data)


def main(out: str) -> int:
    import jax

    from chipbench.program import FIRST_STEPS, Program

    if jax.devices()[0].platform != "tpu":
        print("record_step_trace: needs a TPU", file=sys.stderr)
        return 3
    out = Path(out)
    out.mkdir(parents=True, exist_ok=True)

    median = Program(tiny("smollm-l20-median-w4"))
    median.init_state(0)
    write_gz(out / "step_median.hlo.txt.gz",
             median.compile().as_text().encode())
    median.free()

    prog = Program(tiny("smollm-l20-flag-w4"))
    prog.init_state(0)
    write_gz(out / "step.hlo.txt.gz", prog.compile().as_text().encode())
    prog.first_steps()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    _, steps, _, _ = prog.window(FIRST_STEPS, 0.0, traced_steps=STEPS)
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    write_gz(out / "step.xplane.pb.gz", Path(path).read_bytes())
    shutil.rmtree(tmp)
    for name in ("step.xplane.pb.gz", "step.hlo.txt.gz",
                 "step_median.hlo.txt.gz"):
        print(f"record_step_trace: wrote {out / name} "
              f"({(out / name).stat().st_size} bytes), {steps} steps")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
