#!/usr/bin/env python3
"""Chip smoke test: Byzantine-robust training of smollm-360m on a TPU.

Trains smollm-360m at every published width (d_model 960, 15 heads, 5 KV
heads, d_ff 2560, vocab 49152; fp32 params and Adam, bf16 compute) with
W = 4 workers, one of them Byzantine (sign flip), the Flag Aggregator and
2048-token sequences, through the normal entry point
``repro.launch.train.main``.  One v5e chip (16 GiB) cannot hold the four
per-worker gradient stacks of all 32 layers, so the depth is cut to
``LAYERS``; no width and no worker is cut.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: sharded aggregation

One chip: checks the Pallas aggregation against the XLA reference on a
seeded gradient pytree shaped like the model, then trains 3 steps and
checks that every loss is finite and that the compiled step runs the
Pallas Gram and combine kernels (``tpu_custom_call``).

``--chips 4``: trains 2 steps with ``--sharded-agg`` on a (data=2,
model=2) mesh of the four chips, then 2 steps on one device, in this
process, and checks that losses and parameters agree.  Both runs use SGD
with momentum: parameters are then linear in the aggregated update, so
fp32 agreement of the parameters is fp32 agreement of the aggregation.
Adam is not: it divides each coordinate by its own magnitude, so where
the aggregate of a coordinate is near zero, an fp32 reassociation of the
partial-Gram psum moves that coordinate by up to twice the learning rate
(measured on four v5e chips: 3.3e-3 = 2 x lr, with identical losses).
Both losses are taken on identical parameters (the warmup gives step 0 a
learning rate of 0), so they differ only by the order of fp32 sums.  A
third loss would not: parameters that differ by an fp32 rounding can
round to different bf16 values in the forward pass (measured: 1.2e-5
relative at step 2 with parameters within 4.5e-7).

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.  Without a TPU the script says so
and exits non-zero; there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

LAYERS = 20          # deepest cut whose train step compiles under ~14 GB
WORKERS = 4
TRAIN_ARGV = [
    "--arch", "smollm-360m", "--layers", str(LAYERS), "--seq", "2048",
    "--workers", str(WORKERS), "--per-worker-batch", "1",
    "--aggregator", "flag", "--byzantine", "1", "--attack", "sign_flip",
    "--log-every", "1",
]
AGG_RTOL = 5e-4      # pallas vs xla, relative to the largest magnitude
SHARDED_STEPS = 2
SHARDED_LOSS_RTOL = 1e-6    # same parameters, fp32 sums in another order
SHARDED_PARAM_ATOL = 1e-6   # fp32 rounding of O(1) parameters


def _max_rel(a, b):
    """max |a - b| over max |b|, both pytrees of arrays (on device)."""
    import jax
    import jax.numpy as jnp
    diffs = jax.tree.leaves(jax.tree.map(
        lambda x, y: jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))), a, b))
    mags = jax.tree.leaves(jax.tree.map(
        lambda y: jnp.max(jnp.abs(y.astype(jnp.float32))), b))
    return float(max(diffs)) / max(float(max(mags)), 1e-30)


def aggregation_check(cfg, workers: int, seed: int = 0) -> bool:
    """``aggregate_tree`` with ``impl="pallas"`` against ``impl="xla"`` on
    one seeded worker-major gradient pytree shaped like ``cfg``'s params.

    Honest workers share a mean gradient plus their own noise, as real
    workers do (i.i.d. noise alone would make the Gram nearly a multiple
    of the identity and the FA weights ill-conditioned); worker 0 sends
    its gradient flipped and scaled by 4 (a Byzantine worker).  Both paths
    run under fp32 matmul precision, so they must agree on the FA weights
    and on the update to fp32 rounding.
    """
    import jax
    import jax.numpy as jnp

    from repro.dist.aggregation import AggregatorConfig, aggregate_tree
    from repro.models import transformer

    shapes = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree.flatten(shapes)

    @jax.jit
    def make(key):
        out = []
        for k, s in zip(jax.random.split(key, len(leaves)), leaves):
            k_mean, k_noise = jax.random.split(k)
            mean = jax.random.normal(k_mean, s.shape, jnp.float32)
            noise = jax.random.normal(k_noise, (workers,) + s.shape,
                                      jnp.float32)
            out.append((mean + noise).at[0].multiply(-4.0))
        return treedef.unflatten(out)

    tree = make(jax.random.PRNGKey(seed))
    agg = jax.jit(aggregate_tree, static_argnums=1)
    results = {}
    with jax.default_matmul_precision("highest"):
        for impl in ("pallas", "xla"):
            acfg = AggregatorConfig(name="flag", f=1, impl=impl)
            t0 = time.perf_counter()
            d, aux = agg(tree, acfg)
            jax.block_until_ready(d)
            results[impl] = (d, aux["weights"])
            print(f"aggregation[{impl}]: first call "
                  f"{time.perf_counter() - t0:.1f}s, weights "
                  f"{[round(float(w), 6) for w in aux['weights']]}",
                  flush=True)
    (d_p, c_p), (d_x, c_x) = results["pallas"], results["xla"]
    c_err = _max_rel(c_p, c_x)
    d_err = _max_rel(d_p, d_x)
    ok = c_err <= AGG_RTOL and d_err <= AGG_RTOL
    print(f"aggregation check: weights rel err {c_err:.3e}, update rel err "
          f"{d_err:.3e} (limit {AGG_RTOL:g}) -> "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    return ok


def kernels_in(hlo: str) -> dict[str, int]:
    """Count the Pallas Gram and combine custom calls in compiled HLO."""
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return {name: sum(f"%{name}" in line.split("=")[0] for line in calls)
            for name in ("tree_gram", "weighted_sum")}


def training_phase(argv) -> bool:
    from repro.launch import train

    res = train.main(argv + ["--steps", "3"])
    finite = len(res.losses) == 3 and all(map(math.isfinite, res.losses))
    kernels = kernels_in(res.compiled.as_text())
    has_kernels = all(kernels.values())
    print(f"training: losses {res.losses}, step seconds "
          f"{[round(s, 3) for s in res.step_seconds]}, compile "
          f"{res.compile_seconds:.1f}s", flush=True)
    print(f"training: Pallas kernels in the compiled step {kernels} -> "
          f"{'ok' if has_kernels else 'MISSING'}; losses "
          f"{'finite' if finite else 'NOT FINITE'}", flush=True)
    return finite and has_kernels


def sharded_phase(argv) -> bool:
    """SGD steps with ``--sharded-agg`` on all devices vs one device."""
    import gc

    from unittest import mock

    import jax
    import numpy as np

    from repro.launch import train
    from repro.models import attention

    runs = {}
    for label, extra in (("sharded", ["--sharded-agg"]), ("one-device", [])):
        # on the mesh attention runs in XLA (attention.attend); the
        # one-device run takes the same path, so that the two differ only
        # in the aggregation
        with mock.patch.object(attention, "_kernel_runs_here", lambda: False):
            res = train.main(argv + ["--optimizer", "sgd", "--steps",
                                     str(SHARDED_STEPS)] + extra)
        runs[label] = (res.losses, jax.device_get(res.params))
        del res
        gc.collect()
    (loss_s, p_s), (loss_1, p_1) = runs["sharded"], runs["one-device"]
    loss_err = max(abs(a - b) / max(abs(b), 1e-30)
                   for a, b in zip(loss_s, loss_1))
    p_err = max(float(np.max(np.abs(a - b)))
                for a, b in zip(jax.tree.leaves(p_s), jax.tree.leaves(p_1)))
    ok = (len(loss_s) == len(loss_1) == SHARDED_STEPS
          and all(map(math.isfinite, loss_s + loss_1))
          and loss_err <= SHARDED_LOSS_RTOL and p_err <= SHARDED_PARAM_ATOL)
    print(f"sharded vs one device: losses {loss_s} vs {loss_1} (rel err "
          f"{loss_err:.3e}, limit {SHARDED_LOSS_RTOL:g}); params max abs "
          f"err {p_err:.3e} (limit {SHARDED_PARAM_ATOL:g}) -> "
          f"{'ok' if ok else 'FAILED'}", flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-aggregation comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform "
              f"{dev.platform!r}); this check runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import train
    from repro.launch.compile_cache import enable_compile_cache

    print(f"jax {jax.__version__}; device {dev.device_kind} x "
          f"{len(devices)}; compile cache {enable_compile_cache()}",
          flush=True)
    if args.chips == 4:
        ok = sharded_phase(TRAIN_ARGV)
    else:
        cfg = train.model_config(train.parse_args(TRAIN_ARGV))
        ok = aggregation_check(cfg, WORKERS)
        ok = training_phase(TRAIN_ARGV) and ok
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
