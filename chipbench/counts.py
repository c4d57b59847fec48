"""Operations and bytes of the benchmarked work, counted from shapes.

The counts are algorithmic: they follow from the model configuration and
the traffic (workers, batch, sequence), never from the program's HLO, so
a program change that adds a copy, pads differently or fuses a kernel
leaves them unchanged.  What depends on the architecture (parameters,
attention) is counted by the configuration's reference module.  Peaks
come from ``peaks.json``, keyed by the
``device_kind`` JAX reports; a device that is not in the table is an
error, not a default.
"""

from __future__ import annotations

import json
from pathlib import Path

from chipbench.reference import model as reference_model

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
FP32_BYTES = 4


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peak table entry of one device kind; KeyError if it has none."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def param_count(c: dict) -> int:
    """All parameters of the configuration's architecture."""
    return reference_model(c).param_count(c)


def train_flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs per trained token: 6 per matmul parameter (forward and
    backward; the embedding lookup is not a matmul) plus the
    architecture's causal attention.  Recomputation is not counted."""
    m = reference_model(c)
    return 6.0 * m.matmul_params(c) + m.attention_flops_per_token(c, seq)


def train_flops_per_step(c: dict, traffic: dict) -> float:
    tokens = traffic["workers"] * traffic["per_worker_batch"] * traffic["seq"]
    return tokens * train_flops_per_token(c, traffic["seq"])


def gram_bytes(c: dict, workers: int, devices: int = 1) -> float:
    """HBM bytes the (W, W) Gram must read per device: the fp32 (W, n)
    gradient stack once, split over the devices that share it."""
    return workers * param_count(c) * FP32_BYTES / devices


def combine_bytes(c: dict, workers: int, devices: int = 1) -> float:
    """HBM bytes of the weighted combine per device: W * n reads and n
    writes of fp32, split over the devices that share it."""
    return (workers + 1) * param_count(c) * FP32_BYTES / devices
