"""The distributed train step: per-worker grads -> attack -> aggregate -> update.

One pure function of ``(params, opt_state, batch, rng, step)`` so the whole
pipeline jits (and pjits on a mesh) as a single program:

  1. **Per-worker gradients** — the worker-major batch ``{tokens (W,B,S),
     labels (W,B,S)[, prefix_embeds]}`` goes through ``vmap(value_and_grad)``
     over the worker axis; on a mesh the worker axis shards over
     ``(pod, data)`` so this is ordinary data parallelism.  With
     ``microbatch_splits > 1`` each worker accumulates its gradient over
     sequential micro-batches (a ``lax.scan``), bounding activation memory.
  2. **In-graph attack injection** — ``repro.core.attacks`` corrupts the
     first ``attack_f`` workers' gradients *inside* the graph, so Byzantine
     simulations compile into the same program they benchmark.
  3. **Compression + aggregation** —
     :func:`repro.dist.aggregation.compressed_aggregate`: the optional
     ``repro.comm`` codec compresses each worker's message (sketch codecs
     feed FA's Gram path directly; biased codecs run through error
     feedback), then the rule aggregates.  FA runs in Gram space (the flat
     (W, n) matrix is never materialized).  With ``sharded_agg`` the
     gradient stack is constrained into coordinate shards straight off the
     backward pass (``repro.dist.sharding.shard_grad_stack`` — no
     device-0 hop) and aggregation runs mesh-native
     (:mod:`repro.dist.sharded`): partial-Gram psum, replicated weight
     solve, shard-local combine.
  4. **Update** — ``repro.optim`` transform + ``apply_updates``.

With a non-trivial ``tc.faults`` schedule (:mod:`repro.dist.membership`)
the step additionally computes the round's active-worker mask *in-graph*
from the step index and threads it through the compression + aggregation
stage: every rule operates on the dynamic worker subset (masked Gram rows
/ masked leaves), absent workers ship no bits and keep their EF memory
frozen, and membership changes never recompile (the mask is a traced
value; all shapes stay (W, ...)).

Each stage runs under a ``jax.named_scope`` — ``grad``, ``attack``,
``aggregate``, ``optimizer``, ``telemetry`` — which XLA keeps as the
``op_name`` of every instruction, so a device trace splits into stages
(docs/architecture.md, "Stage scopes"; the names are a contract).

When the configured codec needs error feedback (``tc.comm.wants_ef``) the
step carries the per-worker EF memory explicitly: its signature becomes
``step(params, opt_state, batch, rng, step_idx, ef)`` returning
``(params, opt_state, metrics, ef)``, with ``ef`` initialized by
``repro.comm.init_ef(params, workers)``.  Without EF the signature is the
classic 5-in / 3-out form, unchanged from the uncompressed path.

Metrics: ``loss`` (mean over workers, pre-attack — honest telemetry),
``lr``, ``grad_global_norm`` (of the aggregated update direction),
``fa_weights`` (the (W,) raw combination weights c — the paper's worker
"value" signal), ``worker_influence`` (|c_i| * ||g_i|| normalized to
sum 1: each worker's share of the aggregated update's mass.  Raw c is the
right paper-faithful quantity but misleading under degenerate norms — a
zero-gradient Byzantine worker gets a huge c yet contributes nothing —
so the Byzantine-dominance tests assert on influence), and
``comm_bits`` / ``comm_ratio`` (bits shipped worker->server this step per
the codec's declared cost model, and the fp32-dense ratio).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.comm.compressors import CommConfig
from repro.core import attacks
from repro.dist.aggregation import AggregatorConfig, compressed_aggregate
from repro.dist.membership import FaultSchedule, membership_at
from repro.dist.sharding import shard_grad_stack
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim import Optimizer, apply_updates

__all__ = ["TrainConfig", "init_train_state", "build_train_step",
           "global_norm"]


@dataclass(frozen=True)
class TrainConfig:
    """Distributed-step settings orthogonal to the model config."""

    aggregator: AggregatorConfig = AggregatorConfig()
    attack: str = "none"              # repro.core.attacks registry name
    attack_f: int = 0                 # Byzantine worker count (first f)
    microbatch_splits: int = 1        # grad-accumulation splits per worker
    attn_impl: str = "xla"            # 'xla' (host / dry-run) | 'pallas' (TPU)
    comm: CommConfig = CommConfig()   # worker->server compression (repro.comm)
    faults: FaultSchedule = FaultSchedule()  # worker churn (dist.membership)
    sharded_agg: bool = False         # mesh-sharded aggregation (dist.sharded):
                                      # worker grads go coordinate-sharded by
                                      # construction — partial-Gram psum, no
                                      # device-0 hop, no full (W, n) stack


def init_train_state(key, cfg: ModelConfig, opt: Optimizer):
    """Initialize one model replica's training state.

    Args:
      key: PRNG key for parameter init.
      cfg: the model config.
      opt: the ``repro.optim`` optimizer whose state is initialized.
    Returns:
      ``(params, opt_state)``.  When the train config enables a codec with
      error feedback, the per-worker EF memory is a *third*, separately
      initialized piece of state: ``repro.comm.init_ef(params, workers)``.
    """
    params = transformer.init_params(key, cfg)
    return params, opt.init(params)


def global_norm(tree) -> jnp.ndarray:
    """L2 norm over every leaf of a pytree (fp32)."""
    sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
             for l in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


def build_train_step(cfg: ModelConfig, tc: TrainConfig, opt: Optimizer,
                     sched, *, grad_shardings=None, param_shardings=None):
    """Build the jit-able distributed train step.

    Args:
      cfg: model config (forward/backward definition).
      tc: distributed-step config — aggregator, attack, microbatching, and
        the worker->server compression codec.
      opt: ``repro.optim`` optimizer.
      sched: maps the int32 step index to a learning rate.
      grad_shardings: optional explicit sharding for the worker-major
        gradient pytree (the dry-run passes GSPMD-propagated layouts;
        ``None`` lets XLA choose).
      param_shardings: same, for the updated parameters.
    Returns:
      ``step(params, opt_state, batch, rng, step_idx)`` returning
      ``(new_params, new_opt_state, metrics)`` — unless the codec carries
      error feedback (``tc.comm.wants_ef``), in which case the EF memory is
      an explicit extra carry: ``step(params, opt_state, batch, rng,
      step_idx, ef)`` returning ``(new_params, new_opt_state, metrics,
      new_ef)``, with ``ef`` from ``repro.comm.init_ef(params, workers)``.
    """

    def loss_fn(params, wb):
        return transformer.forward(params, wb, cfg, attn_impl=tc.attn_impl)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def worker_grad(params, wb):
        """Gradient + metrics for ONE worker's (B, ...) batch."""
        k = tc.microbatch_splits
        if k <= 1:
            (_, metrics), g = grad_fn(params, wb)
            return g, metrics
        B = jax.tree.leaves(wb)[0].shape[0]
        if B % k != 0:
            raise ValueError(
                f"microbatch_splits={k} must divide the per-worker batch "
                f"size B={B} (grad accumulation splits the batch into k "
                "equal sequential micro-batches)")
        mb = jax.tree.map(
            lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), wb)
        m_shapes = jax.eval_shape(
            lambda p, b: loss_fn(p, b)[1], params,
            jax.tree.map(lambda x: x[0], mb))

        def accum(carry, b):
            acc_g, acc_m = carry
            (_, m), g = grad_fn(params, b)
            return (jax.tree.map(lambda a, x: a + x.astype(jnp.float32),
                                 acc_g, g),
                    jax.tree.map(jnp.add, acc_m, m)), None

        zeros = (jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params),
                 jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                              m_shapes))
        (g, m), _ = jax.lax.scan(accum, zeros, mb)
        inv = 1.0 / k
        # Accumulation stays fp32; the *output* matches the k<=1 path's
        # param-dtype gradients so the aggregator and comm_bits accounting
        # see the same inputs regardless of k.
        return (jax.tree.map(lambda t, p: (t * inv).astype(p.dtype),
                             g, params),
                jax.tree.map(lambda t: t * inv, m))

    def core(params, opt_state, batch, rng, step_idx, ef):
        with jax.named_scope("grad"):
            grads, metrics_w = jax.vmap(worker_grad, in_axes=(None, 0))(
                params, batch)
            if grad_shardings is not None:
                grads = jax.lax.with_sharding_constraint(grads,
                                                         grad_shardings)

        if tc.attack != "none" and tc.attack_f > 0:
            with jax.named_scope("attack"):
                grads = attacks.apply_attack_tree(tc.attack, grads, rng,
                                                  tc.attack_f)

        W = jax.tree.leaves(grads)[0].shape[0]
        with jax.named_scope("aggregate"):
            if tc.faults.is_trivial:
                mem, mask = None, None
            else:
                # Membership is a pure jnp function of the traced step
                # index: the same compiled program serves every worker
                # subset.
                mem = membership_at(tc.faults, step_idx, W)
                mask = mem.active.astype(jnp.float32)

            if tc.sharded_agg:
                # Sharded by construction: GSPMD redistributes the
                # per-worker gradients straight into the coordinate-shard
                # layout the sharded aggregation consumes — the (W, n)
                # stack never gathers onto one device on its way to the
                # aggregator.
                grads = shard_grad_stack(grads)

            d, agg_aux, new_ef = compressed_aggregate(
                grads, tc.aggregator, tc.comm, ef, mask=mask,
                sharded=tc.sharded_agg or None)

        with jax.named_scope("optimizer"):
            lr = sched(step_idx)
            updates, new_opt_state = opt.update(d, opt_state, params, lr)
            new_params = apply_updates(params, updates)
            if param_shardings is not None:
                new_params = jax.lax.with_sharding_constraint(
                    new_params, param_shardings)

        with jax.named_scope("telemetry"):
            c = agg_aux["weights"].astype(jnp.float32)
            worker_norms = jnp.sqrt(sum(
                jnp.sum(jnp.square(l.astype(jnp.float32)),
                        axis=tuple(range(1, l.ndim)))
                for l in jax.tree.leaves(grads)))
            influence = jnp.abs(c) * worker_norms
            influence = influence / jnp.maximum(jnp.sum(influence), 1e-20)

            if mask is None:
                metrics = {k: jnp.mean(v) for k, v in metrics_w.items()}
            else:
                # honest telemetry: absent workers' slots hold garbage —
                # the per-worker metric means cover the active subset only.
                wa = jnp.maximum(jnp.sum(mask), 1.0)
                metrics = {
                    k: jnp.sum(v * mask.reshape((W,) + (1,) * (v.ndim - 1)))
                    / (wa * (v.size // W))
                    for k, v in metrics_w.items()}
            metrics["lr"] = lr
            metrics["grad_global_norm"] = global_norm(d)
            metrics["fa_weights"] = c
            metrics["worker_influence"] = influence
            metrics["comm_bits"] = agg_aux["comm_bits"]
            metrics["comm_ratio"] = agg_aux["comm_ratio"]
            if mem is not None:
                metrics["active_workers"] = jnp.sum(
                    mem.active.astype(jnp.int32))
                metrics["worker_staleness"] = mem.staleness
        return new_params, new_opt_state, metrics, new_ef

    if tc.comm.wants_ef:
        return core           # ef-carrying signature, 6-in / 4-out

    def step(params, opt_state, batch, rng, step_idx):
        new_params, new_opt_state, metrics, _ = core(
            params, opt_state, batch, rng, step_idx, None)
        return new_params, new_opt_state, metrics

    return step
