"""The public-entry-point sweep: what ``tools/jaxlint.py`` checks.

One place defines which graphs get linted and against which contracts —
the CLI, the CI ``lint-contracts`` lane, and the tier-1 "entry points
are lint-clean" acceptance test (``tests/test_analysis.py``) all consume
:func:`run_sweep`.  Coverage:

* ``fa_weights_from_gram`` (rank-p solver) — SHAPE ``max_dim = p`` on
  the compiled HLO (PR 3's no-q-space invariant), PRECISION, TRANSFER.
* ``aggregate_tree`` for **all 11 rules** × {plain, masked, sketch} —
  PRECISION + TRANSFER on the traced jaxpr; MASK on the masked variant.
* ``compressed_aggregate`` (CountSketch gram-feed and signSGD+EF) —
  PRECISION + TRANSFER + MASK.
* serve path (prefill + one-token decode) on the reduced config at
  **bf16 compute** — PRECISION + TRANSFER (the production inference
  dtype; the fp32 smoke dtype would vacuously pass).
* train step (churn faults, FA aggregator) — PRECISION + TRANSFER.
* RECOMPILE harness — membership, the masked solver, and the serve step
  must hold ``cache_size == 1`` across value sweeps.
* sharded variants (needs >= 8 devices, e.g. under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — per-device
  SHAPE no-full-width + COLLECTIVES byte budget + PRECISION + TRANSFER
  on the compiled, partitioned HLO for all 11 rules.
* **kernel entries** — every production ``pallas_call`` site (gram:
  per-matrix / fused-tree / sketch-stride; coord_stats: plain, the
  meamed key-value sort path, masked, Krum, Bulyan; flash_attn: bf16
  prefill + decode; weighted_sum; plus the full ``aggregate_tree``
  graph at ``impl='pallas_interpret'``, and its sharded twin in the
  mesh block) — linted with the five K-rule families (KTILING / KRACE /
  KVMEM / KPRECISION / KSENTINEL) via
  :func:`repro.analysis.pallas_rules.check_kernels`.  Each entry pins
  the expected site count so the sweep can never pass vacuously on a
  graph that lowered without Pallas.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.findings import Report
from repro.analysis.recompile import check_recompile
from repro.analysis.rules import (Graph, capture, check_collectives,
                                  check_mask, check_precision, check_shape,
                                  check_transfer, full_width_dims)

__all__ = ["SWEEP_RULES", "sweep_entries", "run_sweep"]

W = 8          # worker count for the aggregation entries
SWEEP_RULES = ("mean", "flag", "pca", "median", "trimmed_mean", "meamed",
               "phocas", "krum", "multi_krum", "bulyan", "geomed")


@dataclass(frozen=True)
class Entry:
    name: str
    run: object                       # () -> list[Finding]


def _tree(seed: int = 0):
    """Clean power-of-two widths so the sharded variants divide an
    8-way mesh (1024 + 512 flat; total 1536)."""
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(W, 1024)), jnp.float32),
            "b": {"c": jnp.asarray(rng.normal(size=(W, 256, 2)),
                                   jnp.float32)}}


def _mask():
    return jnp.asarray([1, 0, 1, 1, 0, 1, 1, 1], jnp.float32)


def _agg_cfg(name: str):
    from repro.core.flag import FlagConfig
    from repro.dist.aggregation import AggregatorConfig
    return AggregatorConfig(name=name, f=1,
                            flag=FlagConfig(lam=2.0, m=2, tol=0.0))


def _graph_rules(graph: Graph):
    return check_precision(graph) + check_transfer(graph)


# ---------------------------------------------------------------------------
# entry builders (lazy — nothing traces until Entry.run is called)
# ---------------------------------------------------------------------------

def _gram_solver_entry():
    def run():
        from repro.core.flag import FlagConfig
        from repro.core.gram import fa_weights_from_gram, gram_matrix
        p = 32
        rng = np.random.default_rng(23)
        K = gram_matrix(jnp.asarray(rng.normal(size=(4 * p, p)), jnp.float32))
        cfg = FlagConfig(lam=float(p))
        graph = capture(fa_weights_from_gram, K, cfg,
                        name="fa_weights_from_gram", compile=True)
        return (check_shape(graph, max_dim=p, require_dims={p})
                + _graph_rules(graph))
    return Entry("gram_solver/rank_p(p=32)", run)


def _aggregate_entries():
    from repro.dist.aggregation import GRAM_RULES, aggregate_tree
    entries = []
    for name in SWEEP_RULES:
        variants = ["plain", "masked"]
        if name in GRAM_RULES or name == "bulyan":
            variants.append("sketch")

        for variant in variants:
            def run(name=name, variant=variant):
                tree = _tree()
                cfg = _agg_cfg(name)
                if variant == "sketch":
                    import dataclasses
                    cfg = dataclasses.replace(cfg, sketch_stride=4)
                if variant == "masked":
                    findings = check_mask(
                        lambda m: aggregate_tree(tree, cfg, mask=m),
                        _mask(), name=f"aggregate_tree[{name}]")
                    graph = Graph(
                        f"aggregate_tree[{name}]",
                        jax.make_jaxpr(lambda m: aggregate_tree(
                            tree, cfg, mask=m))(_mask()))
                    return findings + _graph_rules(graph)
                graph = capture(aggregate_tree, tree, cfg,
                                name=f"aggregate_tree[{name}]",
                                compile=False)
                return _graph_rules(graph)

            entries.append(Entry(f"aggregate_tree/{name}/{variant}", run))
    return entries


def _compressed_entries():
    from repro.comm import CommConfig, init_ef
    from repro.dist.aggregation import compressed_aggregate

    def run_sketch():
        tree = _tree(1)
        comm = CommConfig(codec="countsketch", sketch_ratio=0.25)
        findings = check_mask(
            lambda m: compressed_aggregate(tree, _agg_cfg("flag"), comm,
                                           mask=m),
            _mask(), name="compressed_aggregate[countsketch]")
        graph = capture(compressed_aggregate, tree, _agg_cfg("flag"), comm,
                        name="compressed_aggregate[countsketch]",
                        compile=False)
        return findings + _graph_rules(graph)

    def run_ef():
        tree = _tree(2)
        comm = CommConfig(codec="signsgd")
        params = jax.tree.map(lambda l: l[0], tree)
        ef = init_ef(params, W)
        graph = capture(compressed_aggregate, tree, _agg_cfg("mean"), comm,
                        ef, name="compressed_aggregate[signsgd+ef]",
                        compile=False)
        return _graph_rules(graph)

    return [Entry("compressed_aggregate/countsketch/gram-feed", run_sketch),
            Entry("compressed_aggregate/signsgd/ef", run_ef)]


def _serve_entries():
    def _cfg_bf16():
        from repro.configs import get_config, reduce_for_smoke
        return reduce_for_smoke(get_config("smollm-360m")).replace(
            frontend=None, num_prefix_embeds=0, compute_dtype="bfloat16")

    def run_prefill():
        from repro.dist.serve_step import build_prefill_step
        from repro.models import transformer
        cfg = _cfg_bf16()
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
        graph = capture(build_prefill_step(cfg), params, batch,
                        name="prefill_step[bf16]", compile=False)
        return _graph_rules(graph)

    def run_decode():
        from repro.dist.serve_step import build_serve_step
        from repro.models import transformer
        cfg = _cfg_bf16()
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        caches = transformer.init_caches(cfg, 2, 32, jnp.float32)
        graph = capture(build_serve_step(cfg, max_len=32), params, caches,
                        jnp.zeros((2, 1), jnp.int32),
                        jnp.zeros((), jnp.int32),
                        name="serve_step[bf16]", compile=False)
        return _graph_rules(graph)

    return [Entry("serve/prefill/bf16", run_prefill),
            Entry("serve/decode/bf16", run_decode)]


def _train_entry():
    def run():
        from repro.configs import get_config, reduce_for_smoke
        from repro.core.flag import FlagConfig
        from repro.dist.aggregation import AggregatorConfig
        from repro.dist.membership import get_fault_schedule
        from repro.dist.train_step import (TrainConfig, build_train_step,
                                           init_train_state)
        from repro.optim import constant, sgd
        cfg = reduce_for_smoke(get_config("smollm-360m")).replace(
            frontend=None, num_prefix_embeds=0)
        Wt = 4
        tc = TrainConfig(
            aggregator=AggregatorConfig(
                name="flag", flag=FlagConfig(lam=0.0, regularizer="none")),
            faults=get_fault_schedule("churn", Wt, period=2, horizon=16))
        opt = sgd(momentum=0.9)
        params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        step = build_train_step(cfg, tc, opt, constant(1e-3))
        rng = np.random.default_rng(7)
        batch = {
            "tokens": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (Wt, 2, 16)), jnp.int32),
            "labels": jnp.asarray(
                rng.integers(0, cfg.vocab_size, (Wt, 2, 16)), jnp.int32)}
        graph = capture(step, params, opt_state, batch,
                        jax.random.PRNGKey(1), jnp.zeros((), jnp.int32),
                        name="train_step[flag+churn]", compile=False)
        return _graph_rules(graph)

    return Entry("train_step/flag/churn", run)


def _recompile_entries():
    def run_membership():
        from repro.dist.membership import get_fault_schedule, membership_at
        sched = get_fault_schedule("churn", 4, period=3, horizon=30)
        f = jax.jit(lambda t: membership_at(sched, t, 4))
        return check_recompile(
            f, [(jnp.asarray(t, jnp.int32),) for t in range(6)],
            name="membership_at")

    def run_masked_solver():
        from repro.core.flag import FlagConfig
        from repro.core.gram import fa_weights_from_gram, gram_matrix
        rng = np.random.default_rng(3)
        K = gram_matrix(jnp.asarray(rng.normal(size=(32, W)), jnp.float32))
        cfg = FlagConfig(lam=2.0, m=2, tol=0.0)
        f = jax.jit(lambda k, m: fa_weights_from_gram(k, cfg, mask=m))
        masks = [np.ones(W), np.r_[np.zeros(2), np.ones(W - 2)],
                 np.r_[np.ones(W - 3), np.zeros(3)]]
        return check_recompile(
            f, [(K, jnp.asarray(m, jnp.float32)) for m in masks],
            name="fa_weights_from_gram[masked]")

    def run_serve():
        from repro.configs import get_config, reduce_for_smoke
        from repro.dist.serve_step import build_serve_step
        from repro.models import transformer
        cfg = reduce_for_smoke(get_config("smollm-360m")).replace(
            frontend=None, num_prefix_embeds=0)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        caches = transformer.init_caches(cfg, 1, 16, jnp.float32)
        f = jax.jit(build_serve_step(cfg, max_len=16))
        tok = jnp.zeros((1, 1), jnp.int32)
        variants = []
        for t in range(3):
            variants.append((params, caches, tok, jnp.asarray(t, jnp.int32)))
        return check_recompile(f, variants, name="serve_step")

    return [Entry("recompile/membership_at", run_membership),
            Entry("recompile/fa_weights_masked", run_masked_solver),
            Entry("recompile/serve_step", run_serve)]


def _kernel_entries():
    """The K-rule block: one entry per production kernel configuration.

    Kernels are traced with ``interpret=True`` (or
    ``impl='pallas_interpret'``) so the ``pallas_call`` primitive is
    present in the jaxpr on every backend — the CPU ``impl='pallas'``
    dispatch deliberately lowers to plain XLA, which would leave the
    K-rules nothing to look at.  ``n_sites`` pins the expected site
    count (detector sanity).
    """
    from repro.analysis.pallas_rules import check_kernels

    def _ck(fn, *args, n_sites: int, mask_inputs=None, name: str = ""):
        jaxpr = jax.make_jaxpr(fn)(*args)
        return check_kernels(jaxpr, name=name, expect_sites=n_sites,
                             mask_inputs=mask_inputs)

    def _gm(seed=0, n=4096, p=15, dtype=jnp.float32):
        rng = np.random.default_rng(seed)
        return jnp.asarray(rng.normal(size=(n, p)), dtype)

    def run_gram():
        from repro.kernels.gram.kernel import gram_pallas
        return _ck(lambda g: gram_pallas(g, block_n=1024, interpret=True),
                   _gm(), n_sites=1, name="gram_pallas")

    def run_tree_gram(stride=1):
        from repro.kernels.gram.kernel import tree_gram_pallas
        X = jnp.asarray(
            np.random.default_rng(1).normal(size=(W, 5000)), jnp.float32)
        return _ck(lambda x: tree_gram_pallas(
            x, sketch_stride=stride, block_n=1024, interpret=True),
            X, n_sites=1, name=f"tree_gram_pallas[stride={stride}]")

    def run_coord(op, masked=False):
        from repro.kernels.coord_stats.kernel import coord_stats_pallas
        Gw = jnp.asarray(
            np.random.default_rng(2).normal(size=(15, 5000)), jnp.float32)
        if masked:
            mask = jnp.asarray(np.r_[np.ones(12), np.zeros(3)], jnp.float32)
            return _ck(lambda g, m: coord_stats_pallas(
                g, m, op=op, f=3, interpret=True), Gw, mask,
                n_sites=1, mask_inputs=(1,),
                name=f"coord_stats[{op},masked]")
        return _ck(lambda g: coord_stats_pallas(
            g, op=op, f=3, interpret=True), Gw,
            n_sites=1, name=f"coord_stats[{op}]")

    def run_krum():
        from repro.kernels.coord_stats.kernel import krum_scores_pallas
        D2 = jnp.asarray(
            np.random.default_rng(3).normal(size=(15, 15))**2, jnp.float32)
        return _ck(lambda d: krum_scores_pallas(d, f=3, interpret=True),
                   D2, n_sites=1, name="krum_scores_pallas")

    def run_bulyan():
        from repro.kernels.coord_stats.kernel import bulyan_select_pallas
        D2 = jnp.asarray(
            np.random.default_rng(4).normal(size=(15, 15))**2, jnp.float32)
        return _ck(lambda d: bulyan_select_pallas(d, f=3, interpret=True),
                   D2, n_sites=1, name="bulyan_select_pallas")

    def run_flash(decode=False):
        from repro.kernels.flash_attn.kernel import flash_attn_pallas
        rng = np.random.default_rng(5)
        sq, sk = (1, 512) if decode else (256, 384)
        q = jnp.asarray(rng.normal(size=(2, 2, sq, 64)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(2, 2, sk, 64)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(2, 2, sk, 64)), jnp.bfloat16)
        return _ck(lambda q, k, v: flash_attn_pallas(
            q, k, v, causal=not decode, interpret=True), q, k, v,
            n_sites=1,
            name=f"flash_attn[{'decode' if decode else 'prefill'},bf16]")

    def run_wsum():
        from repro.kernels.weighted_sum.kernel import weighted_sum_pallas
        rng = np.random.default_rng(6)
        G = jnp.asarray(rng.normal(size=(W, 5000)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(W,)), jnp.float32)
        return _ck(lambda g, cc: weighted_sum_pallas(g, cc, interpret=True),
                   G, c, n_sites=1, name="weighted_sum_pallas")

    def run_aggregate_interp():
        import dataclasses
        from repro.dist.aggregation import aggregate_tree
        tree = _tree(8)
        cfg = dataclasses.replace(_agg_cfg("flag"),
                                  impl="pallas_interpret")
        return _ck(lambda t: aggregate_tree(t, cfg), tree,
                   # fused tree Gram + one weighted combine per leaf
                   n_sites=3,
                   name="aggregate_tree[flag,pallas_interpret]")

    return [
        Entry("kernels/gram/plain", run_gram),
        Entry("kernels/gram/tree", lambda: run_tree_gram(1)),
        Entry("kernels/gram/tree_sketch", lambda: run_tree_gram(4)),
        Entry("kernels/coord_stats/median", lambda: run_coord("median")),
        Entry("kernels/coord_stats/meamed_kv", lambda: run_coord("meamed")),
        Entry("kernels/coord_stats/masked",
              lambda: run_coord("median", masked=True)),
        Entry("kernels/coord_stats/krum", run_krum),
        Entry("kernels/coord_stats/bulyan", run_bulyan),
        Entry("kernels/flash_attn/prefill_bf16", lambda: run_flash(False)),
        Entry("kernels/flash_attn/decode_bf16", lambda: run_flash(True)),
        Entry("kernels/weighted_sum/plain", run_wsum),
        Entry("kernels/aggregate/flag_interpret", run_aggregate_interp),
    ]


def _sharded_entries():
    entries = []
    for name in SWEEP_RULES:
        def run(name=name):
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.dist.aggregation import aggregate_tree
            from repro.dist.sharded import coord_axes, n_coord_shards
            from repro.launch.mesh import make_host_mesh
            tree = _tree()
            mesh = make_host_mesh(8)
            shards = n_coord_shards(mesh)
            axes = coord_axes(mesh)
            forbidden, required = full_width_dims(tree, shards)
            specs = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(
                    l.shape, l.dtype,
                    sharding=NamedSharding(
                        mesh, P(None, axes, *([None] * (l.ndim - 2))))),
                tree)
            cfg = _agg_cfg(name)
            hlo = jax.jit(
                lambda t: aggregate_tree(t, cfg, sharded=mesh)).lower(
                    specs).compile().as_text()
            graph = Graph(f"aggregate_tree[{name},sharded]", None, hlo)
            n_flat = sum(
                math.prod(l.shape[1:]) for l in jax.tree.leaves(tree))
            # budget: the wire story is O(n + W^2) per device — one (W, W)
            # psum for the Gram plus at most one n-sized redistribution of
            # the combined update; a naive W*n gradient exchange busts it.
            budget = 4.0 * n_flat * 2 + 4.0 * W * W * 64
            return (check_shape(graph, forbidden_dims=forbidden,
                                require_dims=required)
                    + check_collectives(graph, shards,
                                        max_bytes_per_device=budget)
                    + check_precision(graph) + check_transfer(graph))

        entries.append(Entry(f"aggregate_tree/{name}/sharded", run))

    def run_sharded_kernels():
        import dataclasses
        from repro.analysis.pallas_rules import check_kernels
        from repro.dist.sharded import sharded_aggregate_tree
        from repro.launch.mesh import make_host_mesh
        tree = _tree(9)
        mesh = make_host_mesh(8)
        cfg = dataclasses.replace(_agg_cfg("flag"),
                                  impl="pallas_interpret")
        jaxpr = jax.make_jaxpr(
            lambda t: sharded_aggregate_tree(t, cfg, mesh=mesh))(tree)
        # shard-local fused Gram + one weighted combine per leaf, all
        # inside the shard_map body
        return check_kernels(jaxpr, expect_sites=3,
                             name="sharded_aggregate_tree[flag,interp]")

    entries.append(Entry("kernels/aggregate/sharded_interpret",
                         run_sharded_kernels))
    return entries


def sweep_entries(*, sharded: str = "auto") -> list[Entry]:
    """Every lintable entry point.

    ``sharded``: ``'auto'`` includes the mesh variants iff >= 8 devices
    are visible, ``'force'`` includes them unconditionally, ``'skip'``
    leaves them out (the single-device tier-1 path — CI runs them in the
    lint lane under a forced 8-device host platform).
    """
    entries = ([_gram_solver_entry()] + _aggregate_entries()
               + _compressed_entries() + _serve_entries() + [_train_entry()]
               + _recompile_entries() + _kernel_entries())
    want_sharded = (sharded == "force"
                    or (sharded == "auto" and jax.device_count() >= 8))
    if want_sharded:
        entries += _sharded_entries()
    return entries


def run_sweep(*, sharded: str = "auto", names=None,
              progress=None) -> Report:
    """Run the sweep; returns a :class:`Report` (``.clean`` gates CI)."""
    report = Report()
    for entry in sweep_entries(sharded=sharded):
        if names and not any(s in entry.name for s in names):
            continue
        if progress:
            progress(entry.name)
        report.add(entry.name, entry.run())
    return report
