"""One module per per-layer metric, named as in BENCHMARK.json.

Each module has ``read(ctx) -> float | None``; ``ctx`` is a
``chipbench.metrics.Context``.  A reader that finds nothing to read
returns None and the metric is left out of the result line.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass

from chipbench.trace import Trace


@dataclass
class Context:
    trace: Trace
    steps: int               # steps inside the traced window
    config: dict
    traffic: dict
    chips: int
    peaks: dict
    step_module: str         # name of the train step's XLA module

    @property
    def coord_devices(self) -> int:
        """Devices that share the gradient stack's coordinates."""
        return self.chips if self.config["sharded_agg"] else 1

    def per_step_ms(self, pattern: str) -> float | None:
        """Mean over devices of the device time of matching ops per step,
        or None where no such op ran."""
        secs = [self.trace.op_seconds(pattern, d) for d in self.trace.devices]
        total = sum(secs) / len(secs)
        return total / self.steps * 1e3 if total > 0 else None


def read(name: str, ctx: Context):
    return importlib.import_module(f"chipbench.metrics.{name}").read(ctx)
