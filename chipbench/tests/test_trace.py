"""The trace reduction, on a small trace recorded on a v5e chip
(``record_trace.py``: the program's Pallas Gram, combine and
coordinate-median kernels, three rounds, inside a ``chipbench.window``
annotation) and on a hand-made one whose answers are known."""

from pathlib import Path

import pytest

from chipbench.trace import Trace, op_name

RECORDED = Path(__file__).resolve().parent / "data" / "kernels.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.exists():      # made by record_trace.py on a chip
        pytest.skip(f"no recorded chip trace at {RECORDED.name} yet")
    return Trace.from_xplane(RECORDED)


def test_recorded_trace_has_the_chip_and_the_window(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    assert 0 < recorded.window_s < 5
    assert [h[0] for h in recorded.host].count("chipbench.feed") == 3


@pytest.mark.parametrize("pattern", [r"\btree_gram\b", r"\bweighted_sum\b",
                                     r"\bcoord_stats_pallas\b"])
def test_kernels_are_found_by_name(recorded, pattern):
    import re
    dev = recorded.devices[0]
    events = [e for e in dev.ops if re.search(pattern, e[0])]
    assert len(events) == 3
    assert all(" " not in e[0] and not e[0].startswith("%") for e in events)
    lo, hi = recorded.window
    inside = sum(min(s + d, hi) - max(s, lo) for _, s, d in events
                 if s < hi and s + d > lo)
    assert recorded.op_seconds(pattern, dev) == pytest.approx(inside * 1e-9)
    assert recorded.op_seconds(pattern, dev) > 0


def test_busy_union_is_disjoint_and_bounded(recorded):
    dev = recorded.devices[0]
    iv = recorded.busy_intervals(dev)
    assert all(a[1] < b[0] for a, b in zip(iv, iv[1:]))
    busy = recorded.busy_seconds(dev)
    kernels = (recorded.op_seconds(r"\btree_gram\b", dev)
               + recorded.op_seconds(r"\bweighted_sum\b", dev))
    assert kernels <= busy + 1e-12
    assert busy <= recorded.window_s
    assert busy == pytest.approx(sum(e - s for s, e in iv) * 1e-9)


def test_json_round_trip(recorded):
    again = Trace.from_json(recorded.to_json())
    assert again.window == recorded.window
    assert again.mean_busy_seconds() == recorded.mean_busy_seconds()


HAND = {
    "window": [100, 1100],
    "host": [["chipbench.window", 100, 1000], ["chipbench.feed", 0, 400],
             ["chipbench.sync", 700, 400]],
    "devices": [
        {"name": "/device:TPU:0",
         "ops": [["fusion.1", 50, 100],        # clipped to 100..150
                 ["tree_gram", 140, 60],       # overlaps fusion.1
                 ["weighted_sum.3", 400, 100],
                 ["all-reduce.2", 600, 50],
                 ["copy", 1050, 200]],         # clipped to 1050..1100
         "modules": [["jit_step(7)", 50, 500], ["jit_step(8)", 600, 100],
                     ["jit_gen(1)", 900, 10]]},
        {"name": "/device:TPU:1",
         "ops": [["tree_gram", 100, 500]], "modules": []},
    ],
}


def test_op_name_is_the_instruction_not_its_operands():
    text = ("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %weighted_sum.3), "
            "kind=kLoop, calls=%fused_computation")
    assert op_name(text) == "fusion.7"
    assert op_name("%tree_gram.1 = f32[4,4]{1,0} custom-call()") == "tree_gram.1"
    assert op_name("copy.2") == "copy.2"


def test_hand_made_trace():
    t = Trace.from_json(HAND)
    d0, d1 = t.devices
    assert t.window_s == pytest.approx(1000e-9)
    assert t.busy_intervals(d0) == [[100, 200], [400, 500], [600, 650],
                                    [1050, 1100]]
    assert t.busy_seconds(d0) == pytest.approx(300e-9)
    assert t.mean_busy_seconds() == pytest.approx(400e-9)
    assert t.op_seconds(r"\btree_gram\b", d0) == pytest.approx(60e-9)
    assert t.op_seconds(r"\bweighted_sum\b", d0) == pytest.approx(100e-9)
    assert t.op_seconds("all-reduce", d0) == pytest.approx(50e-9)
    assert t.module_seconds("jit_step", d0) == pytest.approx(550e-9)
    top = dict(t.top_ops(3))
    assert top["tree_gram"] == pytest.approx((60 + 500) * 1e-9 / 2)
    gaps = t.idle_gaps(2)
    assert gaps[0] == ["sync", pytest.approx(400e-9)]     # 650..1050
    assert gaps[1][1] == pytest.approx(200e-9)            # 200..400
    assert gaps[1][0] == "feed"
