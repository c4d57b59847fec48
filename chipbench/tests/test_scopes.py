"""The reduction of a trace to stage times (``chipbench/scopes.py``): on a
hand-made trace whose answers are known, on the train-step trace recorded
on a v5e chip (``record_step_trace.py``), and through the readers.  The
accepted readers read the same values as before on ``kernels.xplane.pb``.
"""

import gzip
from pathlib import Path

import pytest

from chipbench import counts, scopes, spec
from chipbench.metrics import Context, read
from chipbench.tests import tiny
from chipbench.trace import Trace

DATA = Path(__file__).resolve().parent / "data"
NEW = ("fwd_ms", "bwd_ms", "remat_ms", "attention_ms", "mlp_ms",
       "lm_head_ms", "attack_ms", "optimizer_ms", "telemetry_ms",
       "unscoped_ms", "feed_ms", "pack_ms", "fa_solve_ms")

GRAD = "jit(step)/grad/vmap(jvp())/while"
BWD = "jit(step)/grad/vmap(transpose(jvp()))/while/body"
HAND_SCOPES = {
    "while.1": GRAD,
    "fusion.1": GRAD + "/body/closed_call/attention/dot_general",
    "fusion.2": BWD + "/closed_call/checkpoint/mlp/dot_general",
    "fusion.3": BWD + "/closed_call/checkpoint/rematted_computation/"
                      "attention/exp",
    "tree_gram.1": "jit(step)/aggregate/gram/jit(tree_gram_pallas)/"
                   "tree_gram/pallas_call",
    "fusion.4": "jit(step)/optimizer/add",
    "fusion.6": "jit(step)/attack/jit(_where)/select_n",
}
HAND = {
    "window": [0, 1000],
    "host": [["chipbench.window", 0, 1000]],
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [["fusion.1", 20, 60],          # the feed's: not the step's
                ["while.1", 100, 300],         # holds fusion.1..3
                ["fusion.1", 120, 80],
                ["fusion.2", 200, 100],
                ["fusion.3", 310, 80],
                ["tree_gram.1", 400, 50],
                ["copy.1", 450, 30],           # no scope
                ["fusion.4", 600, 100],
                ["fusion.6", 700, 150]],
        "modules": [["jit_gen(3)", 20, 60], ["jit_step(1)", 100, 400],
                    ["jit_step(2)", 600, 300]]}],
}
HAND_NS = {"fwd": 40 + 80, "bwd": 100, "remat": 80, "attention": 160,
           "mlp": 100, "gram": 50, "unscoped": 30, "optimizer": 100,
           "attack": 150, "feed": 60}


def test_classify():
    c = scopes.classify
    assert c(HAND_SCOPES["fusion.1"]) == scopes.OpClass("grad", "attention",
                                                      "fwd", None)
    assert c(HAND_SCOPES["fusion.2"]).direction == "bwd"
    assert c(HAND_SCOPES["fusion.3"]).direction == "remat"
    assert c("jit(step)/grad/vmap(jvp(lm_head))/mul").part == "lm_head"
    assert c("jit(step)/aggregate/gram/pack/concatenate").sub == "pack"
    assert c("jit(step)/aggregate/solve/jit(eigh)/eigh").sub == "solve"
    assert c("jit(step)/aggregate/coord_stats/jit(coord_stats_pallas)/"
             "coord_stats_pallas/pallas_call").sub == "coord_stats"
    assert c("jit(step)/attention/closed_call/iota") == scopes.OpClass(
        None, "attention", None, None)       # hoisted out of ``grad``
    assert c("") == scopes.OpClass(None, None, None, None)


def test_self_times_sum_to_the_union():
    assert scopes.self_times([(0, 10), (2, 4), (4, 6)]) == [6, 2, 2]
    assert scopes.self_times([(0, 5), (0, 5)]) == [0, 5]
    # partial overlap: the later start owns the overlap
    assert scopes.self_times([(0, 6), (4, 10), (12, 13)]) == [4, 6, 1]
    assert scopes.self_times([]) == []


def test_hand_made_trace():
    t = Trace.from_json(HAND)
    table, busy = scopes.step_table(t, "jit_step", HAND_SCOPES)
    ns = {k: v * 1e9 for k, v in table.items()}
    by = lambda **m: sum(v for c, v in ns.items()   # noqa: E731
                         if all(getattr(c, k) == x for k, x in m.items()))
    assert by(stage="grad", direction="fwd") == pytest.approx(HAND_NS["fwd"])
    assert by(stage="grad", direction="bwd") == pytest.approx(HAND_NS["bwd"])
    assert by(stage="grad", direction="remat") == pytest.approx(
        HAND_NS["remat"])
    assert by(part="attention") == pytest.approx(HAND_NS["attention"])
    assert by(part="mlp") == pytest.approx(HAND_NS["mlp"])
    assert by(sub="gram") == pytest.approx(HAND_NS["gram"])
    assert by(stage=None) == pytest.approx(HAND_NS["unscoped"])
    assert by(stage="optimizer") == pytest.approx(HAND_NS["optimizer"])
    assert by(stage="attack") == pytest.approx(HAND_NS["attack"])
    assert busy * 1e9 == pytest.approx(630)
    assert sum(ns.values()) == pytest.approx(busy * 1e9)
    assert scopes.other_module_seconds(t, "jit_step") * 1e9 == \
        pytest.approx(HAND_NS["feed"])


def _context(trace, steps, step_module, cell):
    return Context(trace=trace, steps=steps, config=cell.config,
                   traffic=cell.traffic, chips=1,
                   peaks=counts.peaks("TPU v5 lite"), step_module=step_module)


def test_readers_on_the_hand_made_trace(monkeypatch):
    monkeypatch.setattr(scopes, "step_scopes", lambda cell: HAND_SCOPES)
    ctx = _context(Trace.from_json(HAND), 2, "jit_step", tiny.cell())
    want = {"fwd_ms": "fwd", "bwd_ms": "bwd", "remat_ms": "remat",
            "attention_ms": "attention", "mlp_ms": "mlp",
            "attack_ms": "attack", "optimizer_ms": "optimizer",
            "unscoped_ms": "unscoped", "feed_ms": "feed"}
    for name in NEW:
        ns = HAND_NS.get(want.get(name), 0)
        assert read(name, ctx) == pytest.approx(ns * 1e-9 / 2 * 1e3), name


def test_readers_read_zero_where_the_program_names_no_scopes(monkeypatch):
    """A program older than the scopes: every scope reader reads 0.0 and
    none reads None, which ``run.py`` would take for a fault."""
    monkeypatch.setattr(scopes, "step_scopes", lambda cell: {})
    ctx = _context(Trace.from_json(HAND), 2, "jit_step", tiny.cell())
    for name in NEW:
        v = read(name, ctx)
        if name == "feed_ms":
            assert v == pytest.approx(60e-9 / 2 * 1e3)
        else:
            assert v == 0.0, name


def test_every_new_metric_is_in_the_benchmark():
    bench = spec.benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in NEW:
        m = per_layer[name]
        assert (m["unit"], m["source"], m["better"], m["moves"]) == (
            "ms", "device_trace", "lower", "tokens_per_s")
        want = (["smollm-l20-flag-w4"] if name in ("pack_ms", "fa_solve_ms")
                else cells)
        assert m["workloads"] == want


def test_step_scopes_compiles_the_cells_step():
    """The readers' own path to the scope map: the cell's step compiled
    again through ``program.Program``, here at tiny size on the CPU (where
    the Gram takes the XLA path, which does not pack)."""
    found = scopes.step_scopes(tiny.cell())
    paths = set(found.values())
    for name in scopes.STAGES + scopes.PARTS + ("gram", "solve", "combine"):
        assert any(name in scopes._TOKEN.findall(p) for p in paths), name


# -- the recorded train step (record_step_trace.py) ---------------------------

def _gunzip(name: str) -> bytes:
    return gzip.decompress((DATA / name).read_bytes())


@pytest.fixture(scope="module")
def step(tmp_path_factory):
    from repro.analysis.hlo import attributed_scopes

    xplane = tmp_path_factory.mktemp("step") / "step.xplane.pb"
    xplane.write_bytes(_gunzip("step.xplane.pb.gz"))
    trace = Trace.from_xplane(xplane)
    text = _gunzip("step.hlo.txt.gz").decode()
    module = text.split("\n", 1)[0].split()[1].rstrip(",")
    return trace, module, attributed_scopes(text), text


def test_recorded_step_ops_are_the_step_modules_instructions(step):
    trace, module, found, text = step
    table, busy = scopes.step_table(trace, module, found)
    assert busy > 0
    dev = trace.devices[0]
    mods = [(s, e) for n, s, e in trace._clip(dev.modules)
            if n.split("(")[0] == module]
    assert len(mods) == 2
    inside = [n for n, s, _ in trace._clip(dev.ops)
              if any(a <= s < b for a, b in mods)]
    assert inside and all(f"%{n} = " in text for n in inside)


def test_recorded_step_classes_sum_to_busy_time(step):
    trace, module, found, _ = step
    table, busy = scopes.step_table(trace, module, found)
    assert abs(sum(table.values()) - busy) < 1e-9
    assert busy <= sum(trace.module_seconds(module, d)
                       for d in trace.devices) + 1e-9


def test_recorded_step_has_fwd_bwd_and_remat(step):
    trace, module, found, _ = step
    table, _ = scopes.step_table(trace, module, found)
    for direction in ("fwd", "bwd", "remat"):
        assert sum(t for c, t in table.items()
                   if c.stage == "grad" and c.direction == direction) > 0


def test_recorded_step_leaves_little_unscoped(step):
    """XLA's own instructions carry no ``op_name``; on this step they are
    8% of the busy time, 1% once they take their neighbours' scope."""
    from repro.analysis.hlo import op_scopes

    trace, module, found, text = step

    def unscoped_share(scope_map):
        table, busy = scopes.step_table(trace, module, scope_map)
        return sum(t for c, t in table.items() if c.stage is None) / busy

    assert unscoped_share(op_scopes(text)) > 0.05
    assert unscoped_share(found) < 0.02


@pytest.mark.parametrize("hlo,kernel,scope", [
    ("step.hlo.txt.gz", "tree_gram", "/aggregate/gram/"),
    ("step.hlo.txt.gz", "weighted_sum", "/aggregate/combine/"),
    ("step_median.hlo.txt.gz", "coord_stats_pallas",
     "/aggregate/coord_stats/"),
])
def test_chip_kernels_sit_under_their_scope(hlo, kernel, scope):
    from repro.analysis.hlo import op_scopes

    found = op_scopes(_gunzip(hlo).decode())
    kernels = {n: p for n, p in found.items()
               if n == kernel or n.startswith(kernel + ".")}
    assert kernels
    assert all(scope in p for p in kernels.values()), kernels


# -- the accepted readers, unchanged ------------------------------------------

# Read by the readers of the benchmark as it was before the stage metrics,
# with the smollm-360m-l20 flag cell's configuration: the rooflines count
# that cell's bytes against this trace's small kernels, so only their
# sameness means anything.
KERNELS_READ = {
    "idle_share": 98.33784507156771, "step_device_ms": 0.28757766666666673,
    "mfu": 188.851527136589, "gram_ms": 0.2875743333333333,
    "gram_hbm_roofline": 1656.45232187332, "combine_ms": 0.14941333333333334,
    "combine_hbm_roofline": 3985.1963135918163,
    "coord_stats_ms": 0.11031466666666667,
    "coord_stats_hbm_roofline": 5397.663639783153,
}


def test_accepted_readers_unchanged_on_the_kernel_trace():
    t = Trace.from_xplane(DATA / "kernels.xplane.pb")
    cell = spec.load_cell("smollm-l20-flag-w4")
    ctx = _context(t, 3, "jit_tree_gram_pallas", cell)
    for name, value in KERNELS_READ.items():
        assert read(name, ctx) == value, name
