"""A run with the timed path broken underneath reports ``correct`` false.

Each test drives ``run.measure`` (everything but the look for a chip) on
a CPU-sized cell held to the benchmark cell's limits, with one fault
planted in the program: a step that returns its state unchanged; half of
each worker's tokens left out of the loss, the mean taken over the rest;
a token altered where the data pipeline produces it.  On a four-device
mesh a sound run is correct; no cell of the benchmark runs on a mesh,
so the exchange between devices left out is a fault for the four-chip
cell to catch with its own numbers (PERF.md, Open questions).
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests import tiny

SEED = 2_147_483_659


def measure(cell, seed=SEED):
    return run.measure(cell, seed, 0.5, False, t_start=time.perf_counter())


def plant_state_unchanged(monkeypatch):
    from repro.dist import train_step
    build = train_step.build_train_step

    def broken(*a, **kw):
        step = build(*a, **kw)

        def frozen(params, opt_state, *rest):
            _, _, m = step(params, opt_state, *rest)
            return params, opt_state, m
        return frozen
    monkeypatch.setattr(train_step, "build_train_step", broken)


def plant_half_batch(monkeypatch):
    from repro.models import transformer
    forward = transformer.forward

    def half(params, batch, cfg, **kw):
        S = batch["tokens"].shape[-1]
        mask = jnp.broadcast_to(jnp.arange(S) < S // 2,
                                batch["tokens"].shape)
        return forward(params, {**batch, "loss_mask": mask}, cfg, **kw)
    monkeypatch.setattr(transformer, "forward", half)


def plant_token_altered(monkeypatch):
    from repro.data import pipeline
    batches = pipeline.lm_worker_batches

    def altered(task, cfg, step, seq_len, seed=0):
        # half the vocabulary away: a state that the traffic's unigram
        # table maps to another token, whatever the table
        b = batches(task, cfg, step, seq_len, seed=seed)
        tok = b["tokens"]
        return {**b, "tokens": tok.at[0, 0, 1].set(
            (tok[0, 0, 1] + task.vocab_size // 2) % task.vocab_size)}
    monkeypatch.setattr(pipeline, "lm_worker_batches", altered)


@pytest.mark.parametrize("workload", ["smollm-l20-flag-w4",
                                      "smollm-l20-median-w4"])
def test_sound_run_is_correct(workload):
    r = measure(tiny.cell(workload))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("plant,number", [
    (plant_state_unchanged, {"flag": "change_gap", "median": "change_gap"}),
    (plant_half_batch, {"flag": "loss_gap_01", "median": "grad_gap"}),
    (plant_token_altered, {"flag": "tokens_mismatch",
                           "median": "tokens_mismatch"}),
])
@pytest.mark.parametrize("workload", ["smollm-l20-flag-w4",
                                      "smollm-l20-median-w4"])
def test_fault_is_not_correct(monkeypatch, plant, number, workload):
    plant(monkeypatch)
    r = measure(tiny.cell(workload))
    assert not r["correct"]
    c = r["checks"][number[workload.split("-")[2]]]
    assert c["value"] > c["limit"], r["checks"]


EXCHANGE = r"""
import json, sys, time
sys.path[:0] = {paths!r}
from chipbench import run
from chipbench.tests import tiny
r = run.measure(tiny.cell("smollm-l20-flag-w4", chips=4), {seed}, 0.5,
                False, t_start=time.perf_counter())
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
"""


def test_sound_run_on_a_mesh_is_correct():
    root = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = EXCHANGE.format(paths=[str(root / "src"), str(root)], seed=SEED)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
