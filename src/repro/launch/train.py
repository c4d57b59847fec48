"""Training launcher.

Trains the selected architecture on the synthetic LM task with ``--workers``
simulated data-parallel workers (some of them Byzantine with ``--attack``)
and the chosen robust aggregator.  Without ``--debug`` the model keeps every
published width; ``--debug`` trains the reduced variant on the CPU.  The
run uses the devices this process holds: one device, or with
``--sharded-agg`` a (data, model) mesh over all local devices.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --debug --steps 100 --aggregator flag --attack random --byzantine 2

``--layers N`` cuts the depth (never a width) where one device cannot
hold the whole model; the cut is printed in the run header.

``--steps`` is the *total* training horizon: a resumed run (``--ckpt-dir``
pointing at existing checkpoints) completes the remaining steps on the
original LR schedule — the horizon is persisted in the checkpoint meta, so
the warmup/decay shape cannot silently re-warm on the leftover step count.
With a compression codec that carries error feedback (``--codec signsgd``
/ ``topk``) the EF memory is part of the checkpointed state, so a resumed
compressed run keeps its error memory instead of restarting from zero.
Worker churn is injected with ``--faults`` (see repro.dist.membership);
the fault-injection *process-kill* scenarios live in
``repro.launch.elastic``.
"""

from __future__ import annotations

import argparse
import contextlib
import time
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import (checkpoint_meta, latest_step, load_checkpoint,
                              save_checkpoint)
from repro.comm import CODECS, CommConfig, init_ef
from repro.configs import get_config, reduce_for_smoke
from repro.core.flag import FlagConfig
from repro.data.pipeline import WorkerDataConfig, lm_worker_batches
from repro.data.synthetic import SyntheticLM
from repro.dist.aggregation import AggregatorConfig
from repro.dist.membership import FAULTS, get_fault_schedule
from repro.dist.sharding import use_sharding
from repro.dist.train_step import TrainConfig, build_train_step, init_train_state
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim import adamw, sgd, warmup_cosine

# Data parallelism over workers: parameters are replicated on every device,
# so the model's tensor dims stay unsharded and only the worker axis (and,
# for the aggregation, the gradient coordinates) spreads over the mesh.
DATA_PARALLEL_RULES = {k: None for k in (
    "vocab", "mlp", "qkv", "heads", "kv_heads", "expert_mlp", "state")}


@dataclass
class TrainResult:
    """What a caller needs to check a run."""

    losses: list[float]          # per step, mean over workers
    step_seconds: list[float]    # wall time per step (mean per log line)
    compile_seconds: float
    compiled: Any                # the AOT-compiled step (``as_text()``)
    params: Any                  # final parameters


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--debug", action="store_true",
                    help="reduced config (CPU)")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: keep the first N layers, every width "
                         "unchanged (0 = the config's depth)")
    ap.add_argument("--steps", type=int, default=100,
                    help="TOTAL training horizon (resume completes it)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--per-worker-batch", type=int, default=4)
    ap.add_argument("--aggregator", default="flag")
    ap.add_argument("--attack", default="none")
    ap.add_argument("--byzantine", type=int, default=0)
    ap.add_argument("--codec", default="none", choices=("none",) + CODECS)
    ap.add_argument("--no-ef", action="store_true",
                    help="disable error feedback for biased codecs")
    ap.add_argument("--faults", default="none", choices=sorted(FAULTS),
                    help="worker-churn scenario (repro.dist.membership)")
    ap.add_argument("--sharded-agg", action="store_true",
                    help="mesh-sharded aggregation (repro.dist.sharded) "
                         "over a (data, model) mesh of the local devices: "
                         "coordinate shards per device, partial-Gram psum, "
                         "no full (W, n) stack on any device")
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgd"])
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--lam", type=float, default=-1.0,
                    help="FA lambda (-1 = auto: p if p>6 else 0)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def model_config(args, *, cut: bool = True):
    """The model the run trains: published widths, or the reduced variant
    under ``--debug``; ``--layers`` changes ``num_layers`` only."""
    cfg = get_config(args.arch)
    if args.debug:
        cfg = reduce_for_smoke(cfg).replace(frontend=None,
                                            num_prefix_embeds=0)
    if cut and args.layers:
        cfg = cfg.replace(num_layers=args.layers)
    return cfg


def train_config(args) -> TrainConfig:
    W = args.workers
    lam = args.lam if args.lam >= 0 else (float(W) if W > 6 else 0.0)
    return TrainConfig(
        aggregator=AggregatorConfig(
            name=args.aggregator, f=args.byzantine, impl="pallas",
            flag=FlagConfig(lam=lam,
                            regularizer="pairwise" if lam else "none")),
        attack=args.attack, attack_f=args.byzantine, attn_impl="pallas",
        comm=CommConfig(codec=args.codec,
                        error_feedback=False if args.no_ef else None),
        faults=get_fault_schedule(args.faults, W),
        sharded_agg=args.sharded_agg)


def _memory_line(compiled) -> str:
    ma = compiled.memory_analysis()
    if ma is None:
        return "memory: not reported by this backend"
    gb = 1e9
    args_b = ma.argument_size_in_bytes
    out_b = ma.output_size_in_bytes
    tmp_b = ma.temp_size_in_bytes
    alias_b = ma.alias_size_in_bytes
    return (f"memory: args {args_b / gb:.3f} GB, outputs {out_b / gb:.3f} "
            f"GB, temps {tmp_b / gb:.3f} GB, aliased {alias_b / gb:.3f} GB, "
            f"code {ma.generated_code_size_in_bytes / gb:.3f} GB -> "
            f"total {(args_b + out_b + tmp_b - alias_b) / gb:.3f} GB")


def main(argv=None) -> TrainResult:
    args = parse_args(argv)
    enable_compile_cache()
    cfg = model_config(args)
    full_layers = model_config(args, cut=False).num_layers
    tc = train_config(args)
    comm = tc.comm
    W = args.workers
    mesh = make_host_mesh() if args.sharded_agg else None
    opt = adamw() if args.optimizer == "adamw" else sgd(momentum=0.9)

    params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    ef = init_ef(params, W) if comm.wants_ef else None

    total = args.steps
    step0 = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        # The LR horizon is a property of the *run*, not of this process
        # invocation: schedules must be rebuilt on the persisted total, or
        # a resumed run re-warms and re-decays on the leftover step count.
        saved_total = checkpoint_meta(args.ckpt_dir)["extra"].get(
            "total_steps")
        if saved_total is not None and saved_total != total:
            print("resume: using checkpointed horizon total_steps="
                  f"{saved_total} (ignoring --steps {total})")
            total = saved_total
        template = ((params, opt_state, ef) if comm.wants_ef
                    else (params, opt_state))
        flat = jax.tree_util.tree_flatten_with_path(template)[0]
        want = sorted(jax.tree_util.keystr(p) for p, _ in flat)
        saved = checkpoint_meta(args.ckpt_dir)["keys"]
        if saved != want:
            raise SystemExit(
                "resume state mismatch: the checkpoint holds "
                f"{len(saved)} leaves but this invocation expects "
                f"{len(want)} — most likely the --codec/--no-ef flags "
                "differ from the run that wrote the checkpoint (the EF "
                "memory is part of the checkpointed state); rerun with "
                "the original flags or start a fresh --ckpt-dir")
        state, step0 = load_checkpoint(args.ckpt_dir, template)
        if comm.wants_ef:
            params, opt_state, ef = state
        else:
            params, opt_state = state
        print(f"resumed from step {step0}")
    extra = {"total_steps": total}

    # Place the state where the step runs.  On a mesh every device holds a
    # replica and the worker axis of each batch splits over ``data``; the
    # compiled step keeps the replicated layout on its outputs, so step
    # t + 1 takes exactly what step t returned.
    if mesh is None:
        def place(x, batch=False):
            return x
        out_shardings = None
    else:
        rep = NamedSharding(mesh, P())
        by_worker = (NamedSharding(mesh, P("data"))
                     if W % mesh.shape["data"] == 0 else rep)

        def place(x, batch=False):
            return jax.device_put(x, by_worker if batch else rep)
        out_shardings = rep
        params, opt_state, ef = place((params, opt_state, ef))

    sched = warmup_cosine(args.lr, total, warmup=min(20, total // 5))
    # params and optimizer state (and EF memory) are replaced every step:
    # donating them lets the update write in place.
    donate = (0, 1, 5) if comm.wants_ef else (0, 1)
    step_fn = jax.jit(build_train_step(cfg, tc, opt, sched),
                      donate_argnums=donate, out_shardings=out_shardings)
    task = SyntheticLM(vocab_size=cfg.vocab_size)
    wdc = WorkerDataConfig(workers=W, per_worker_batch=args.per_worker_batch)

    def step_args(t):
        batch = place(lm_worker_batches(task, wdc, t, args.seq), batch=True)
        tail = (place(jax.random.PRNGKey(t)),
                place(jnp.asarray(t, jnp.int32)))
        return (params, opt_state, batch) + tail + (
            (ef,) if comm.wants_ef else ())

    def ckpt_tree():
        return (params, opt_state, ef) if comm.wants_ef \
            else (params, opt_state)

    depth = (f"{cfg.num_layers}/{full_layers} (depth cut)"
             if cfg.num_layers != full_layers else f"{cfg.num_layers}")
    devices = (f"mesh {dict(mesh.shape)}" if mesh is not None
               else f"1 device ({jax.devices()[0].device_kind})")
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"layers={depth} d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"seq={args.seq} per_worker_batch={args.per_worker_batch} "
          f"workers={W} agg={args.aggregator}(lam={tc.aggregator.flag.lam}, "
          f"impl={tc.aggregator.impl}) attack={args.attack} "
          f"f={args.byzantine} codec={args.codec} faults={args.faults} "
          f"sharded_agg={args.sharded_agg} on {devices} "
          f"steps {step0}->{total}", flush=True)

    losses = []                  # device scalars until the run ends
    step_seconds: list[float] = []
    ctx = (use_sharding(mesh, DATA_PARALLEL_RULES) if mesh is not None
           else contextlib.nullcontext())
    with ctx:
        t0 = time.perf_counter()
        compiled = step_fn.lower(*step_args(step0)).compile()
        compile_s = time.perf_counter() - t0
        print(f"compiled train step in {compile_s:.1f}s; "
              f"{_memory_line(compiled)}", flush=True)
        t_run = t_mark = time.perf_counter()
        for t in range(step0, total):
            out = compiled(*step_args(t))
            if comm.wants_ef:
                params, opt_state, m, ef = out
            else:
                params, opt_state, m = out
            losses.append(m["loss"])
            if t % args.log_every == 0 or t == total - 1:
                # the host waits for the device only here: wall time per
                # step is the mean over the steps since the last log line
                loss = float(m["loss"])
                now = time.perf_counter()
                n = len(losses) - len(step_seconds)
                step_seconds += [(now - t_mark) / n] * n
                t_mark = now
                act = (f" act {int(m['active_workers'])}/{W}"
                       if "active_workers" in m else "")
                print(f"step {t:5d} loss {loss:.4f} "
                      f"lr {float(m['lr']):.2e} "
                      f"|g| {float(m['grad_global_norm']):.3f}{act} "
                      f"step {step_seconds[-1]:.3f}s "
                      f"({now - t_run:.0f}s)", flush=True)
            if args.ckpt_dir and (t + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, t + 1, ckpt_tree(),
                                extra=extra)
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, total, ckpt_tree(), extra=extra)
    return TrainResult(losses=[float(x) for x in losses],
                       step_seconds=step_seconds,
                       compile_seconds=compile_s, compiled=compiled,
                       params=params)


if __name__ == "__main__":
    main()
