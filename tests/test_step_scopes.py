"""The train step's stage scopes survive compilation.

Each stage of ``dist/train_step.py`` runs under a ``jax.named_scope``
(docs/architecture.md, "Stage scopes"), and the benchmark puts the
profiler's op events back into stages through the ``op_name`` of each
instruction of the *optimized* HLO (``repro.analysis.hlo.op_scopes``,
filled in for the instructions XLA makes without one by
``attributed_scopes``).
Here the real step is compiled at toy widths for the FA and the median
rules, with the Pallas kernels in interpret mode (``impl="pallas"`` takes
the XLA references off the chip and never packs), and every stage, model
part and aggregation sub-stage must still name instructions after XLA's
passes.  The sharded step is compiled on a forced 4-device host mesh in a
subprocess, where its Gram ``psum`` must sit under ``aggregate/gram``.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo import attributed_scopes, op_scopes
from repro.dist.aggregation import AggregatorConfig
from repro.dist.train_step import TrainConfig, build_train_step, init_train_state
from repro.models.config import ModelConfig
from repro.optim import adamw, constant

STAGES = ("grad", "attack", "aggregate", "optimizer", "telemetry")
PARTS = ("embed", "attention", "mlp", "lm_head")
SUBSTAGES = {"flag": ("pack", "gram", "solve", "combine"),
             "median": ("coord_stats",)}
# Step instructions whose path names no stage: the loop-invariant
# attention masks that JAX hoists out of the layer scan, whose path keeps
# ``attention`` but loses ``grad`` (33 of 3334 under FA and 33 of 3900
# under the median at these widths).  Instructions XLA adds without any
# path (copies, loop bookkeeping) are not in the map at all.
UNSCOPED_SHARE = 0.02

W, B, S = 4, 1, 32
CFG = ModelConfig(name="tiny-scopes", arch_type="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=128, compute_dtype="float32")


def names(path: str) -> list[str]:
    return re.findall(r"[\w.\-]+", path)


def compiled_step_text(rule: str) -> str:
    tc = TrainConfig(aggregator=AggregatorConfig(name=rule, f=1,
                                                 impl="pallas_interpret"),
                     attack="sign_flip", attack_f=1)
    opt = adamw()
    params, opt_state = init_train_state(jax.random.PRNGKey(0), CFG, opt)
    batch = {"tokens": jnp.zeros((W, B, S), jnp.int32),
             "labels": jnp.zeros((W, B, S), jnp.int32)}
    step = jax.jit(build_train_step(CFG, tc, opt, constant(1e-3)))
    return step.lower(params, opt_state, batch, jax.random.PRNGKey(1),
                      jnp.asarray(0, jnp.int32)).compile().as_text()


@pytest.fixture(scope="module", params=["flag", "median"])
def compiled(request):
    return request.param, compiled_step_text(request.param)


@pytest.fixture(scope="module")
def scoped(compiled):
    rule, text = compiled
    return rule, op_scopes(text)


def test_every_stage_and_part_names_instructions(scoped):
    rule, scopes = scoped
    found = {n for p in scopes.values() for n in names(p)}
    for scope in STAGES + PARTS + SUBSTAGES[rule]:
        assert scope in found, f"{rule}: no instruction under {scope!r}"
    other = {"flag": "coord_stats", "median": "gram"}[rule]
    assert other not in found


def test_backward_and_recompute_are_told_apart(scoped):
    _, scopes = scoped
    grad = [p for p in scopes.values() if "grad" in names(p)]
    remat = [p for p in grad if "rematted_computation" in p]
    bwd = [p for p in grad if "transpose(" in p and p not in remat]
    fwd = [p for p in grad if "transpose(" not in p]
    assert remat and bwd and fwd
    assert all("transpose(" in p for p in remat)  # recompute runs in the bwd


def test_few_step_instructions_have_no_stage(scoped):
    rule, scopes = scoped
    step = [p for p in scopes.values() if p.startswith("jit(step)/")]
    bare = [p for p in step if not set(names(p)) & set(STAGES)]
    assert len(bare) <= UNSCOPED_SHARE * len(step), (rule, len(bare),
                                                     len(step), bare[:10])


def test_attribution_keeps_every_named_path_and_fills_the_rest(compiled):
    _, text = compiled
    named, every = op_scopes(text), attributed_scopes(text)
    assert all(every[k] == v for k, v in named.items())
    instructions = re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=", text,
                              re.M)
    assert len(named) < len(instructions)
    # left without a path: constants the step returns as metrics, and
    # their copies (6 of 5212 instructions under FA at these widths)
    left = set(instructions) - set(every)
    assert len(left) <= 0.01 * len(instructions), sorted(left)


HAND_HLO = """HloModule m

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4] get-tuple-element(%p), index=1
  %y = f32[4] negate(%x)
  ROOT %t = (s32[], f32[4]) tuple(%i, %y)
}

%cond (q: (s32[], f32[4])) -> pred[] {
  %q = (s32[], f32[4]) parameter(0)
  ROOT %c = pred[] constant(false)
}

ENTRY %main (a: f32[4]) -> (f32[4], f32[4]) {
  %a = f32[4] parameter(0)
  %g = f32[4] multiply(%a, %a), metadata={op_name="jit(step)/grad/mul"}
  %copy = f32[4] copy(%g)
  %k = f32[4] add(%copy, %copy), metadata={op_name="jit(step)/aggregate/gram/add"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[4]) tuple(%zero, %g)
  %loop = (s32[], f32[4]) while(%init), condition=%cond, body=%body
  %u = f32[4] get-tuple-element(%loop), index=1
  %o = f32[4] subtract(%u, %a), metadata={op_name="jit(step)/optimizer/sub"}
  %out = f32[4] copy(%k)
  ROOT %r = (f32[4], f32[4]) tuple(%o, %out)
}
"""


def test_attribution_by_consumer_producer_and_loop():
    every = attributed_scopes(HAND_HLO)
    assert every["copy"] == "jit(step)/aggregate/gram/add"   # what it feeds
    assert every["loop"] == "jit(step)/optimizer/sub"
    assert every["y"] == "jit(step)/optimizer/sub"   # the loop that runs it
    assert every["out"] == "jit(step)/aggregate/gram/add"    # what feeds it
    assert op_scopes(HAND_HLO) == {"g": "jit(step)/grad/mul",
                                   "k": "jit(step)/aggregate/gram/add",
                                   "o": "jit(step)/optimizer/sub"}


SHARDED = r"""
import re, sys
import jax, jax.numpy as jnp
from repro.analysis.hlo import op_scopes
from repro.dist.aggregation import AggregatorConfig
from repro.dist.sharding import use_sharding
from repro.dist.train_step import TrainConfig, build_train_step, init_train_state
from repro.launch.mesh import make_host_mesh
from repro.models.config import ModelConfig
from repro.optim import adamw, constant

cfg = ModelConfig(name="tiny-scopes", arch_type="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=128, compute_dtype="float32")
tc = TrainConfig(aggregator=AggregatorConfig(name="flag", f=1),
                 attack="sign_flip", attack_f=1, sharded_agg=True)
opt = adamw()
params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
batch = {k: jnp.zeros((4, 1, 32), jnp.int32) for k in ("tokens", "labels")}
mesh = make_host_mesh()
assert mesh.devices.size == 4, mesh
with use_sharding(mesh, {k: None for k in (
        "vocab", "mlp", "qkv", "heads", "kv_heads", "expert_mlp", "state")}):
    text = jax.jit(build_train_step(cfg, tc, opt, constant(1e-3))).lower(
        params, opt_state, batch, jax.random.PRNGKey(1),
        jnp.asarray(0, jnp.int32)).compile().as_text()
scopes = op_scopes(text)
for line in text.splitlines():
    m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\S+)\s+all-reduce", line)
    if m:
        print(m.group(2), scopes.get(m.group(1), ""))
"""


def test_sharded_gram_psum_is_under_aggregate_gram():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"))
    r = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    reduces = [line.split(" ", 1) for line in r.stdout.splitlines()]
    gram = [path for shape, path in reduces
            if shape.startswith(f"f32[{W},{W}]")]
    assert gram, r.stdout
    assert all("/aggregate/gram/" in path for path in gram), gram
