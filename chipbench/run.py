#!/usr/bin/env python3
"""Runs one benchmark cell once, on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell's train step (``program.py``), makes the weights on the
device from the seed, compiles ahead of time through the program's fixed
compile cache (``<checkout>/.jax_cache``), runs the first steps that the
reference follows, then measures: with ``--trace 0`` steps for
``--seconds`` and the cell's end-to-end metrics; with ``--trace 1`` one
profiled log block of steps and the cell's per-layer metrics.  Once the
window has closed and the program's state is freed, the plain reference
follows the same first steps and ``check.py`` decides ``correct``.  The
last stdout line is one JSON object; the compared numbers, each with its
limit, are the last lines on stderr and the last key of that object.

With no TPU, or fewer chips than the cell asks for, it prints no result
and exits 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
for p in (CHECKOUT / "src", CHECKOUT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

NO_CHIP = 3


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_ok(cell, devices) -> str:
    """Why this machine cannot run the cell, or '' when it can."""
    if devices[0].platform != "tpu":
        return (f"no TPU: JAX reports platform {devices[0].platform!r}; "
                "the benchmark runs only on the chip")
    if len(devices) < cell.chips:
        return f"{cell.name} needs {cell.chips} chips, JAX finds {len(devices)}"
    return ""


def configure_cache():
    """Cache every compiled program, small ones too, in the program's
    fixed directory inside the checkout."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


def hbm_peak_bytes(device) -> int:
    """Peak device memory: the largest the arrays ever held together
    (``peak_bytes_in_use``) plus the most the runtime ever reserved for
    the programs' scratch (``peak_bytes_reserved``), which on the TPU
    holds a step's temporaries, the gradient stacks among them, and is
    not counted in use."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def _phase(t_start: float, what: str) -> None:
    print(f"chipbench: {time.perf_counter() - t_start:8.2f}s {what}",
          file=sys.stderr, flush=True)


def measure(cell, seed: int, seconds: float, trace: bool, *,
            t_start: float) -> dict:
    """One run of ``cell``; returns the result object (see module doc)."""
    import jax

    from chipbench import check, counts
    from chipbench.metrics import Context, read
    from chipbench.program import FIRST_STEPS, Program
    from chipbench.reference import train as ref_train
    from chipbench.trace import Trace

    devices = jax.devices()
    used = devices[:cell.chips]
    peaks = counts.peaks(devices[0].device_kind) if trace else None
    _phase(t_start, "jax up")
    prog = Program(cell)
    prog.init_state(seed)
    _phase(t_start, "weights made")
    compiled = prog.compile()
    step_module = compiled.as_text().split("\n", 1)[0].split()[1].rstrip(",")
    _phase(t_start, f"compiled {step_module}")
    first = prog.first_steps()
    setup_s = time.perf_counter() - t_start
    _phase(t_start, f"first steps, losses {first['losses']}; window opens")

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tmp)
        t0, steps, elapsed, losses = prog.window(
            FIRST_STEPS, seconds,
            traced_steps=prog.log_every if trace else 0)
        if trace:
            jax.profiler.stop_trace()
        peak_bytes = max(hbm_peak_bytes(d) for d in used)
        losses = [float(x) for x in jax.device_get(losses)]
        prog.free()
        del prog, compiled
        gc.collect()
        _phase(t_start, f"window closed: {steps} steps in {elapsed:.3f}s")
        tr = Trace.from_dir(tmp) if trace else None
        if trace:
            tr.devices = tr.devices[:cell.chips]     # the chips the cell uses
            _phase(t_start, "trace read")
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    ref = ref_train.run(cell.config, cell.traffic, seed, FIRST_STEPS)
    ref_batches = [tuple(map(jax.device_get, ref_train.lm_stream(
        seed, t, cell.traffic, cell.config["vocab_size"])))
        for t in range(FIRST_STEPS)]
    _phase(t_start, f"reference done, losses {ref['losses']}")
    ok, checks = check.judge(check.numbers(first, ref, ref_batches),
                             cell.limits)
    failed = sum(not math.isfinite(x) for x in losses)

    t = cell.traffic
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    if trace:
        ctx = Context(trace=tr, steps=steps, config=cell.config, traffic=t,
                      chips=cell.chips, peaks=peaks, step_module=step_module)
        metrics = {}
        for m in cell.per_layer:
            v = read(m["name"], ctx)
            if v is None:       # the cell is listed for it: a fault, not a gap
                raise RuntimeError(f"per-layer metric {m['name']} found "
                                   f"nothing to read in {cell.name}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.mean_busy_seconds()
        device["window_s"] = tr.window_s
        extra = {"breakdown": {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}}
    else:
        tokens = steps * t["workers"] * t["per_worker_batch"] * t["seq"]
        metrics = {"tokens_per_s": {"value": tokens / elapsed,
                                    "unit": "tokens/s"},
                   "peak_hbm_gb": {"value": peak_bytes / 1e9, "unit": "GB"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        extra = {}
    return {"correct": ok and failed == 0, "attempted": steps,
            "failed": failed, "metrics": metrics, "device": device, **extra,
            "window": {"first_step": t0, "steps": steps, "seconds": elapsed,
                       "losses_first": first["losses"]},
            "checks": checks}


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    from chipbench import check, spec

    cell = spec.load_cell(args.workload)
    why = chips_ok(cell, jax.devices())
    if why:
        print(f"chipbench: {why}", file=sys.stderr)
        return NO_CHIP
    cache = configure_cache()
    print(f"chipbench: {cell.name} seed {args.seed} on "
          f"{jax.devices()[0].device_kind} x {len(jax.devices())}; compile "
          f"cache {cache}", file=sys.stderr, flush=True)
    result = measure(cell, args.seed, args.seconds, bool(args.trace),
                     t_start=T_START)
    for line in check.report_lines(result["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
