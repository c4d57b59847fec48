import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) lowers + compiles.

For each combination this harness:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. derives per-arch sharding rules (kv-head vs head-dim cache sharding,
     expert- vs expert-mlp parallelism, ...),
  3. AOT-lowers ``init_params`` to obtain the GSPMD-propagated parameter
     shardings *without allocating* (command-r fp32 params would be 120GB),
  4. lowers + compiles the real train_step / prefill_step / serve_step with
     those shardings against ShapeDtypeStruct inputs,
  5. records memory_analysis, cost_analysis, and the per-collective byte
     volumes parsed from the partitioned HLO,
  6. writes one JSON per combination under --out (benchmarks/roofline.py
     consumes these).

The device-count override above MUST precede any other import that could
initialize jax.  Train shapes lower with the Flag Aggregator ON (that is
the paper's technique in the step); decode shapes lower ``serve_step``
(one token against a full-length or ring KV cache); ``long_500k`` uses the
documented SWA-4096 variant for full-attention archs (DESIGN.md §6).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun [--scan-layers] [--agg flag]
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPES, input_specs
from repro.core.flag import FlagConfig
from repro.dist import serve_step as serve_lib
from repro.dist.aggregation import AggregatorConfig
from repro.dist.sharding import use_sharding
from repro.dist.train_step import TrainConfig, build_train_step
from repro.launch.mesh import make_production_mesh, worker_count
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim import constant, sgd

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def rules_for(cfg: ModelConfig, mesh, *, serving: bool) -> dict:
    """Per-arch logical->mesh overrides (see dist.sharding.DEFAULT_RULES)."""
    model = mesh.shape["model"]
    dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
    rules: dict = {"worker": dp, "batch": dp}
    if serving:
        rules["sub_batch"] = dp          # serve batch = global batch
    rules["heads"] = "model" if cfg.num_heads % model == 0 else None
    if cfg.num_kv_heads % model == 0:
        rules["kv_heads"], rules["head_dim"] = "model", None
    elif cfg.head_dim % model == 0:
        # contraction-sharded KV cache (GQA kv < model axis): shard head_dim
        rules["kv_heads"], rules["head_dim"] = None, "model"
    else:
        rules["kv_heads"], rules["head_dim"] = None, None
    if cfg.moe is not None:
        if cfg.moe.num_experts % model == 0:
            rules["experts"], rules["expert_mlp"] = "model", None   # EP
        else:
            rules["experts"], rules["expert_mlp"] = None, "model"   # TP
    return rules


def variant_for(cfg: ModelConfig, shape_name: str):
    """long_500k on full-attention archs -> sliding-window-4096 variant."""
    if shape_name == "long_500k" and cfg.window is None \
            and cfg.arch_type not in ("ssm", "hybrid"):
        return cfg.replace(window=4096), "swa4096"
    return cfg, ""


def _replicated(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, P(*([None] * len(s.shape)))), spec_tree)


def _batch_shardings(mesh, spec_tree, lead_axes):
    def one(s):
        if s.shape and s.shape[0] % _axes_size(mesh, lead_axes) == 0:
            return NamedSharding(mesh, P(lead_axes,
                                         *([None] * (len(s.shape) - 1))))
        return NamedSharding(mesh, P(*([None] * len(s.shape))))
    return jax.tree.map(one, spec_tree)


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        n *= mesh.shape[a]
    return n


def lower_one(arch: str, shape_name: str, *, multi_pod: bool,
              scan_layers: bool = True, agg: str = "flag",
              sketch_stride: int = 1, zero1: bool = False,
              gram_dtype: str = "float32", microbatch: int = 0,
              extra_rules: dict | None = None):
    """Lower + compile one combination; returns a result dict."""
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    cfg, variant = variant_for(cfg, shape_name)
    cfg = cfg.replace(scan_layers=scan_layers)
    W = worker_count(mesh)
    dp = ("pod", "data") if multi_pod else ("data",)
    serving = shape.kind != "train"
    rules = rules_for(cfg, mesh, serving=serving)
    if extra_rules:
        rules.update(extra_rules)
    if microbatch == 0:  # auto: keep per-microbatch tokens ~<= 16k at 4k seq
        per_worker = shape.global_batch // max(W, 1)
        microbatch = max(1, per_worker // 4) if cfg.d_model >= 4096 else 1
        while per_worker % microbatch:
            microbatch -= 1
    total_devices = mesh.size

    key = jax.random.PRNGKey(0)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "variant": variant, "kind": shape.kind, "workers": W,
        "scan_layers": scan_layers, "aggregator": agg if not serving else "",
        "sketch_stride": sketch_stride, "zero1": zero1,
    }

    with mesh, use_sharding(mesh, rules):
        # --- parameter shardings via AOT (no allocation) ---
        init_fn = lambda k: transformer.init_params(k, cfg)
        init_compiled = jax.jit(init_fn).lower(key).compile()
        p_shardings = init_compiled.output_shardings
        p_specs = jax.eval_shape(init_fn, key)

        if shape.kind == "train":
            opt = sgd(momentum=0.9)
            o_specs = jax.eval_shape(lambda p: opt.init(p), p_specs)
            o_shardings = jax.tree.map(lambda s: s, p_shardings)
            o_shardings = {"mu": o_shardings}
            if zero1:
                # ZeRO-1: additionally shard the optimizer state's first
                # divisible unsharded dim over the data axis.
                def zshard(sh, spec):
                    pspec = list(sh.spec) + [None] * (len(spec.shape)
                                                      - len(sh.spec))
                    for i, (dim, cur) in enumerate(zip(spec.shape, pspec)):
                        if cur is None and dim % _axes_size(mesh, ("data",)) == 0:
                            pspec[i] = "data"
                            break
                    return NamedSharding(mesh, P(*pspec))
                o_shardings = {"mu": jax.tree.map(zshard, p_shardings,
                                                  p_specs)}
            tc = TrainConfig(
                aggregator=AggregatorConfig(
                    name=agg, f=2, flag=FlagConfig(lam=float(W)),
                    sketch_stride=sketch_stride, gram_dtype=gram_dtype),
                attack="none", microbatch_splits=microbatch)
            result["microbatch_splits"] = microbatch

            def wsharding(sh, spec):
                pspec = list(sh.spec) + [None] * (len(spec.shape)
                                                  - len(sh.spec))
                return NamedSharding(mesh, P(dp, *pspec))
            g_shardings = jax.tree.map(wsharding, p_shardings, p_specs)
            step_fn = build_train_step(cfg, tc, opt, constant(1e-3),
                                       grad_shardings=g_shardings,
                                       param_shardings=p_shardings)
            batch_specs = input_specs(cfg, shape, workers=W)
            b_shardings = _batch_shardings(mesh, batch_specs, dp)
            rng_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
            step_spec = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jax.jit(
                step_fn,
                in_shardings=(p_shardings, o_shardings, b_shardings,
                              None, None),
                out_shardings=(p_shardings, o_shardings, None),
            ).lower(p_specs, o_specs, batch_specs, rng_spec, step_spec)

        elif shape.kind == "prefill":
            step_fn = serve_lib.build_prefill_step(cfg)
            batch_specs = input_specs(cfg, shape)
            b_shardings = _batch_shardings(mesh, batch_specs, dp)
            lowered = jax.jit(
                step_fn, in_shardings=(p_shardings, b_shardings),
            ).lower(p_specs, batch_specs)

        else:  # decode
            cache_fn = lambda: transformer.init_caches(
                cfg, shape.global_batch, shape.seq_len, jnp.bfloat16)
            cache_compiled = jax.jit(cache_fn).lower().compile()
            c_shardings = cache_compiled.output_shardings
            c_specs = jax.eval_shape(cache_fn)
            step_fn = serve_lib.build_serve_step(cfg, max_len=shape.seq_len)
            specs = input_specs(cfg, shape)
            tok_spec = specs["tokens"]
            tok_sh = _batch_shardings(mesh, tok_spec, dp)
            lowered = jax.jit(
                step_fn,
                in_shardings=(p_shardings, c_shardings, tok_sh, None),
                out_shardings=(None, c_shardings),
            ).lower(p_specs, c_specs, tok_spec, specs["step"])

        compiled = lowered.compile()

    # --- analyses ---
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    from repro.analysis.hlo import parse_collectives, parse_cost
    coll = parse_collectives(hlo, total_devices)
    hcost = parse_cost(hlo)

    result.update({
        "ok": True,
        "elapsed_s": round(time.time() - t0, 1),
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
        # loop-corrected (while trip counts folded in; see repro.analysis.hlo):
        "flops_corrected_per_device": hcost.flops,
        "hbm_bytes_corrected_per_device": hcost.hbm_bytes,
        "flops_dots_raw_per_device": hcost.raw_flops,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0)
                           + getattr(mem, "argument_size_in_bytes", 0)),
        },
        "collectives": {
            "total_moved_bytes_per_device": coll.total_moved_bytes,
            "per_kind_bytes": coll.per_kind_bytes,
            "per_kind_count": coll.per_kind_count,
        },
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    })
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' (comma-separated ok)")
    ap.add_argument("--shape", default="all",
                    help="shape name or 'all' (comma-separated ok)")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer stack (bigger HLO, slower "
                         "compile; collective counts are loop-corrected "
                         "either way via repro.analysis.hlo)")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="grad-accumulation splits per worker (0 = auto)")
    ap.add_argument("--agg", default="flag")
    ap.add_argument("--sketch-stride", type=int, default=1)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--gram-dtype", default="float32")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    archs = sorted(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                mesh_tag = "multi" if multi_pod else "single"
                name = f"{arch}_{shape_name}_{mesh_tag}"
                if args.tag:
                    name += f"_{args.tag}"
                out_path = os.path.join(args.out, name + ".json")
                if os.path.exists(out_path):
                    print(f"[skip] {name} (exists)")
                    continue
                print(f"[lower] {name} ...", flush=True)
                try:
                    res = lower_one(arch, shape_name, multi_pod=multi_pod,
                                    scan_layers=not args.unroll,
                                    agg=args.agg,
                                    sketch_stride=args.sketch_stride,
                                    zero1=args.zero1,
                                    gram_dtype=args.gram_dtype,
                                    microbatch=args.microbatch)
                    print(f"[ok]    {name}: "
                          f"flops/dev={res['flops_per_device']:.3e} "
                          f"coll/dev={res['collectives']['total_moved_bytes_per_device']/1e6:.1f}MB "
                          f"peak={res['memory']['peak_bytes']/1e9:.2f}GB "
                          f"({res['elapsed_s']}s)", flush=True)
                except Exception as e:
                    res = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_tag, "ok": False,
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-3000:]}
                    failures.append(name)
                    print(f"[FAIL]  {name}: {type(e).__name__}: "
                          f"{str(e)[:300]}", flush=True)
                with open(out_path, "w") as f:
                    json.dump(res, f, indent=1, default=float)

    print(f"\ndone. {len(failures)} failures: {failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
