"""Reduction of a profiler trace (``.xplane.pb``) to device times.

A device plane is one whose name starts with ``/device:TPU:`` and that
has an ``XLA Ops`` line; its ``XLA Modules`` line holds one event per
program execution.  An op event is named by the whole HLO instruction
text; it is kept under the instruction's own name (``tree_gram.1`` of
``%tree_gram.1 = f32[4,4] custom-call(...)``), so that a pattern never
matches an operand.  The window is the host annotation
``chipbench.window`` (the benchmark's own span); host annotations named
``chipbench.<what>`` say what the host was doing.  Every quantity is
clipped to the window.  ``Trace.to_json``/``from_json`` keep a reduced
trace as plain data, which is how the tests hold a recorded one.
"""

from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW = "chipbench.window"
HOST_PREFIX = "chipbench."
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute")


def op_name(event_name: str) -> str:
    """The HLO instruction's own name in a profiler op event's name."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)       # [name, start_ns, dur_ns]
    modules: list = field(default_factory=list)   # [name, start_ns, dur_ns]


@dataclass
class Trace:
    devices: list
    host: list                      # [name, start_ns, dur_ns] annotations
    window: tuple                   # (start_ns, end_ns)

    # -- loading ------------------------------------------------------------
    @classmethod
    def from_xplane(cls, path) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path))
        devices, host = [], []
        for plane in data.planes:
            lines = {line.name: line for line in plane.lines}
            if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
                dev = Device(plane.name)
                dev.ops.extend([op_name(e.name), e.start_ns, e.duration_ns]
                               for e in lines["XLA Ops"].events)
                if "XLA Modules" in lines:
                    dev.modules.extend([e.name, e.start_ns, e.duration_ns]
                                       for e in lines["XLA Modules"].events)
                devices.append(dev)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    host.extend([e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX))
        devices.sort(key=lambda d: d.name)
        spans = [h for h in host if h[0] == WINDOW]
        if not spans:
            raise ValueError(f"{path}: no {WINDOW!r} annotation")
        w = spans[0]
        return cls(devices, host, (w[1], w[1] + w[2]))

    @classmethod
    def from_dir(cls, directory) -> "Trace":
        files = glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise ValueError(f"{directory}: expected one .xplane.pb, found "
                             f"{len(files)}")
        return cls.from_xplane(files[0])

    def to_json(self) -> dict:
        return {"devices": [{"name": d.name, "ops": d.ops,
                             "modules": d.modules} for d in self.devices],
                "host": self.host, "window": list(self.window)}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls([Device(d["name"], d["ops"], d["modules"])
                    for d in obj["devices"]], obj["host"],
                   tuple(obj["window"]))

    # -- reductions ---------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clip(self, events):
        lo, hi = self.window
        for name, start, dur in events:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                yield name, s, e

    def op_seconds(self, pattern: str, device: Device) -> float:
        """Device time of the ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(e - s for name, s, e in self._clip(device.ops)
                   if rx.search(name)) * 1e-9

    def module_seconds(self, module: str, device: Device) -> float:
        """Device time of one program's executions (``jit_step`` and the
        like; a trailing ``(id)`` in the event name is ignored)."""
        return sum(e - s for name, s, e in self._clip(device.modules)
                   if name.split("(")[0] == module) * 1e-9

    def busy_intervals(self, device: Device) -> list:
        """Union of the op intervals, as sorted disjoint (start, end)."""
        out = []
        for _, s, e in sorted(self._clip(device.ops), key=lambda x: x[1]):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    def busy_seconds(self, device: Device) -> float:
        return sum(e - s for s, e in self.busy_intervals(device)) * 1e-9

    def mean_busy_seconds(self) -> float:
        return (sum(self.busy_seconds(d) for d in self.devices)
                / len(self.devices))

    def top_ops(self, n: int = 10) -> list:
        """[[op, seconds]] of the HLO instructions that took most device
        time, summed over their executions, averaged over the devices.  A
        loop's own time includes that of the ops inside it."""
        acc = {}
        for d in self.devices:
            for name, s, e in self._clip(d.ops):
                acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / len(self.devices)] for k, v in top]

    def idle_gaps(self, n: int = 10) -> list:
        """[[what the host was doing, seconds]] of the longest device idle
        gaps on the first device, named by the host annotation that
        overlaps each gap most (``other`` where none does)."""
        busy = self.busy_intervals(self.devices[0])
        lo, hi = self.window
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        spans = [h for h in self.host if h[0] != WINDOW]
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            best, what = 0, "other"
            for name, hs, hd in spans:
                ov = min(e, hs + hd) - max(s, hs)
                if ov > best:
                    best, what = ov, name[len(HOST_PREFIX):]
            out.append([what, (e - s) * 1e-9])
        return out
