"""Finds a cell's files by name: ``BENCHMARK.json`` names each cell's
configuration and traffic; ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``limits/<cell>.json`` hold them."""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def _read(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark() -> dict:
    return _read(CHECKOUT / "BENCHMARK.json")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list      # BENCHMARK.json per_layer entries this cell reports

    def train_argv(self) -> list[str]:
        """Arguments of ``repro.launch.train`` for this cell."""
        c, t = self.config, self.traffic
        argv = ["--arch", c["program_arch"],
                "--layers", str(c["num_hidden_layers"]),
                "--seq", str(t["seq"]),
                "--workers", str(t["workers"]),
                "--per-worker-batch", str(t["per_worker_batch"]),
                "--aggregator", t["aggregator"],
                "--byzantine", str(t["byzantine"]),
                "--attack", t["attack"],
                "--optimizer", t["optimizer"],
                "--lr", repr(t["lr"]),
                "--steps", str(t["total_steps"]),
                "--log-every", str(t["log_every"])]
        if "flag" in t:
            argv += ["--lam", repr(t["flag"]["lam"])]
        if c["sharded_agg"]:
            argv.append("--sharded-agg")
        return argv


def load_cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    config = _read(HERE / "configs" / f"{entry['config']}.json")
    if config["chips"] != entry["chips"]:
        raise ValueError(f"{name}: BENCHMARK.json asks for {entry['chips']} "
                         f"chips, config {entry['config']} for "
                         f"{config['chips']}")
    metrics = [m for m in bench["per_layer"]
               if "workloads" not in m or name in m["workloads"]]
    return Cell(name=name, chips=entry["chips"], config=config,
                traffic=_read(HERE / "traffic" / f"{entry['traffic']}.json"),
                limits=_read(HERE / "limits" / f"{name}.json"),
                per_layer=metrics)
