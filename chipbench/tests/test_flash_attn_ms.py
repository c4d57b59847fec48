"""The ``flash_attn_ms`` reader, on a small trace recorded on a v5e chip
(``record_flash_trace.py``: one grouped-query forward and backward through
the three flash-attention kernels, three rounds) and on the recorded
trace of the aggregation kernels, where no flash kernel ran."""

import re
from pathlib import Path

import pytest

from chipbench.metrics import Context
from chipbench.metrics import flash_attn_ms
from chipbench.trace import Trace

DATA = Path(__file__).resolve().parent / "data"
KERNELS = ("flash_attn_fwd", "flash_attn_bwd_dkv", "flash_attn_bwd_dq")


def _ctx(trace, steps=3):
    return Context(trace=trace, steps=steps, config={}, traffic={}, chips=1,
                   peaks={}, step_module="")


@pytest.fixture(scope="module")
def recorded():
    return Trace.from_xplane(DATA / "flash_attn.xplane.pb")


@pytest.mark.parametrize("kernel", KERNELS)
def test_each_kernel_is_found_by_name_once_a_round(recorded, kernel):
    dev = recorded.devices[0]
    events = [e for e in dev.ops if re.search(rf"\b{kernel}\b", e[0])]
    assert len(events) == 3
    assert recorded.op_seconds(rf"\b{kernel}\b", dev) > 0


def test_reads_the_three_kernels_per_step(recorded):
    dev = recorded.devices[0]
    total = sum(recorded.op_seconds(rf"\b{k}\b", dev) for k in KERNELS)
    got = flash_attn_ms.read(_ctx(recorded))
    assert got == pytest.approx(total / 3 * 1e3)
    assert 0 < got * 1e-3 <= recorded.busy_seconds(dev) / 3


def test_reads_zero_where_no_flash_kernel_ran():
    trace = Trace.from_xplane(DATA / "kernels.xplane.pb")
    assert flash_attn_ms.read(_ctx(trace)) == 0.0
