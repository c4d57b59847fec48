"""Benchmark entry point: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick] [--only name,...]``

Prints ``name,us_per_call,derived`` CSV (benchmarks/common.py) and persists
JSON under results/bench/.  ``--quick`` shrinks step counts so the full
suite finishes in CI time; the EXPERIMENTS.md numbers use the defaults.
"""

from __future__ import annotations

import argparse
import time

from benchmarks import (augmentation, batch_size, byzantine_tolerance,
                        comm_loss, lambda_sweep, membership_churn,
                        other_attacks, scalability)

SUITES = {
    "byzantine_tolerance": lambda q: byzantine_tolerance.run(
        steps=20 if q else 40, fs=(1, 3) if q else (0, 1, 2, 3),
        aggs=("mean", "multi_krum", "flag") if q
        else byzantine_tolerance.AGGS),
    "batch_size": lambda q: batch_size.run(
        steps=20 if q else 35, batches=(32, 128) if q else (32, 64, 128, 256),
        aggs=("flag", "multi_krum") if q else ("flag", "multi_krum",
                                               "bulyan", "median")),
    "comm_loss": lambda q: comm_loss.run(steps=20 if q else 35),
    "augmentation": lambda q: augmentation.run(steps=20 if q else 35),
    "lambda_sweep": lambda q: lambda_sweep.run(
        steps=20 if q else 35, lams=(0.1, 7.0) if q else
        (0.1, 1.0, 3.0, 7.0, 21.0)),
    "membership_churn": lambda q: membership_churn.run(
        steps=16 if q else 40,
        aggs=("flag", "krum", "mean") if q
        else ("flag", "krum", "mean", "median")),
    "other_attacks": lambda q: other_attacks.run(steps=20 if q else 35),
    "scalability": lambda q: scalability.run(steps=10 if q else 25),
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    names = args.only.split(",") if args.only else list(SUITES)
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in names:
        print(f"# === {name} ===", flush=True)
        SUITES[name](args.quick)
    print(f"# total_wall_seconds,{time.time() - t0:.0f},")


if __name__ == "__main__":
    main()
