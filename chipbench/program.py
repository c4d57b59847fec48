"""The system under test, driven as ``repro.launch.train.main`` drives it.

``main`` takes neither a seed nor a deadline, so this module mirrors its
loop (``launch/train.py``, ``main``): the model and train configs from the
cell's argv (``model_config``, ``train_config``), ``make_host_mesh`` and
``DATA_PARALLEL_RULES`` on a mesh, the jitted ``build_train_step`` with
params and optimizer state donated and replicated outputs, the schedule
``warmup_cosine(lr, steps, warmup=min(20, steps // 5))``, the AOT
``lower(...).compile()`` on step 0's arguments, and a host sync only on
the log cadence (``t % log_every == 0``).  Each step's batch comes from
the program's ``lm_worker_batches`` with the run's seed, its chain
states mapped to tokens by the traffic's unigram table
(``reference.train.unigram_table``: Zipfian, as text is).

Two departures, both outside the step: the weights are made by the
benchmark from the seed (``reference.<model>.init``, laid out by
``to_program``; the program's norms are read back through
``from_program``), so that the reference can make the same ones without
taking anything from the program; and ``lm_worker_batches`` runs under
one ``jax.jit``, because called eagerly it compiles a ``lax.scan`` per
worker on every call, and nothing may compile in the measured window.
"""

from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import model as reference_model
from chipbench.reference import train as ref_train

FIRST_STEPS = 3      # set-up steps the reference follows


def _annotate(name):
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


class Program:
    """One compiled train step with its state, built from a cell."""

    def __init__(self, cell):
        from repro.data.pipeline import WorkerDataConfig, lm_worker_batches
        from repro.data.synthetic import SyntheticLM
        from repro.dist.sharding import use_sharding
        from repro.dist.train_step import build_train_step
        from repro.launch import train as launch
        from repro.launch.mesh import make_host_mesh
        from repro.optim import adamw, sgd, warmup_cosine
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.cell = cell
        c, tr = cell.config, cell.traffic
        args = launch.parse_args(cell.train_argv())
        self.cfg = launch.model_config(args)
        self.tc = launch.train_config(args)
        self.W, self.B, self.S = args.workers, args.per_worker_batch, args.seq
        self.log_every = args.log_every
        self._check_config()
        if min(20, args.steps // 5) != tr["warmup"]:
            raise ValueError("traffic warmup differs from the launcher's "
                             f"min(20, steps // 5) = {min(20, args.steps // 5)}")

        self.mesh = make_host_mesh() if args.sharded_agg else None
        self.opt = (adamw() if args.optimizer == "adamw"
                    else sgd(momentum=0.9))
        if self.mesh is None:
            self.rep = self.by_worker = None
            self.ctx = contextlib.nullcontext
        else:
            self.rep = NamedSharding(self.mesh, P())
            self.by_worker = (NamedSharding(self.mesh, P("data"))
                              if self.W % self.mesh.shape["data"] == 0
                              else self.rep)
            mesh = self.mesh
            self.ctx = lambda: use_sharding(mesh, launch.DATA_PARALLEL_RULES)
        sched = warmup_cosine(args.lr, args.steps,
                              warmup=min(20, args.steps // 5))
        self.step_fn = jax.jit(build_train_step(self.cfg, self.tc, self.opt,
                                                sched),
                               donate_argnums=(0, 1),
                               out_shardings=self.rep)   # as main does
        task = SyntheticLM(vocab_size=self.cfg.vocab_size)
        wdc = WorkerDataConfig(workers=self.W,
                               per_worker_batch=self.B)
        shard = ({} if self.by_worker is None
                 else {"out_shardings": self.by_worker})
        table = ref_train.unigram_table(tr["data"], self.cfg.vocab_size)

        def gen(t, seed):
            batch = lm_worker_batches(task, wdc, t, self.S, seed=seed)
            return {k: ref_train.to_tokens(v, table)
                    for k, v in batch.items()}

        self.gen = jax.jit(gen, **shard)
        self.compiled = None
        self.params = self.opt_state = None

        model, period = reference_model(c), self.cfg.period

        def weights(key):
            return model.to_program(model.init(key, c), c, period)

        def norms(tree):        # per leaf, in the reference's layout
            return ref_train.leaf_norms(model.from_program(tree, c, period))

        rep = {} if self.rep is None else {"out_shardings": self.rep}
        self._weights = jax.jit(weights, **rep)
        self._opt_init = jax.jit(self.opt.init, **rep)
        self._norms = jax.jit(norms)
        self._change = jax.jit(lambda p, key: norms(
            jax.tree.map(jnp.subtract, p, weights(key))))

    # -- configuration ------------------------------------------------------
    def _check_config(self):
        """The program runs what the configuration file states."""
        c, m = self.cell.config, self.cfg
        model = reference_model(c)
        want = model.expect(c)
        got = {}
        for k in want:
            v = getattr(m, k)
            got[k] = set(v()) if callable(v) else v
        if got != want:
            diff = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
            raise ValueError(f"program config differs from the file "
                             f"(program, file): {diff}")
        ours = jax.eval_shape(
            lambda k: model.to_program(model.init(k, c), c, m.period),
            jax.random.PRNGKey(0))
        from repro.models import transformer
        theirs = jax.eval_shape(lambda k: transformer.init_params(k, m),
                                jax.random.PRNGKey(0))
        if (jax.tree.structure(ours) != jax.tree.structure(theirs)
                or jax.tree.leaves(ours) != jax.tree.leaves(theirs)):
            raise ValueError("the program's parameter layout differs from "
                             "the reference's to_program layout")

    # -- state and feed -----------------------------------------------------
    def place(self, x):
        return x if self.rep is None else jax.device_put(x, self.rep)

    def init_state(self, seed: int):
        """Params from the seed on the device, optimizer state from them."""
        self.params = self.opt_state = None
        self.seed = seed
        key = self.place(jax.random.PRNGKey(seed))
        self.params = self._weights(key)
        self.opt_state = self._opt_init(self.params)
        # the seed is an argument, not a constant, so that one compiled
        # feed serves every seed; PRNGKey takes its low 32 bits either way
        self.seed_arg = jnp.uint32(seed % 2**32)

    def step_args(self, t: int):
        with _annotate("feed"):
            batch = self.gen(t, self.seed_arg)
            tail = (self.place(jax.random.PRNGKey(t)),
                    self.place(jnp.asarray(t, jnp.int32)))
        return (self.params, self.opt_state, batch) + tail

    def compile(self):
        with self.ctx():
            self.compiled = self.step_fn.lower(*self.step_args(0)).compile()
        return self.compiled

    def step(self, t: int):
        args = self.step_args(t)
        with self.ctx(), _annotate("dispatch"):
            self.params, self.opt_state, m = self.compiled(*args)
        return m, args[2]

    # -- the first steps, which the reference follows ----------------------
    def first_steps(self) -> dict:
        """Steps 0..FIRST_STEPS-1 through the window's own call and feed.

        Returns each step's loss, the per-leaf norms of the first gradient
        as the optimizer got it (from Adam's first moment after step 0),
        the per-leaf norms of the parameters' change after the last step,
        and the batches that were fed.
        """
        b1 = self.cell.traffic["adamw"]["b1"]
        losses, batches, first_grad = [], [], None
        for t in range(FIRST_STEPS):
            m, batch = self.step(t)
            losses.append(m["loss"])
            batches.append(batch)
            if t == 0:
                first_grad = np.asarray(self._norms(self.opt_state["mu"])) / (
                    1.0 - b1)
        key = self.place(jax.random.PRNGKey(self.seed))
        change = np.asarray(self._change(self.params, key))
        return {"losses": [float(x) for x in losses],
                "first_grad": first_grad, "change": change,
                "batches": [jax.device_get(b) for b in batches]}

    # -- the measured window ------------------------------------------------
    def window(self, t0: int, seconds: float, *, traced_steps: int = 0):
        """Steps from ``t0`` on the launcher's cadence until ``seconds``
        have passed on the host clock, then until the next sync.

        With ``traced_steps`` the window is exactly the steps between two
        syncs (one log block), under the annotation ``chipbench.window``.
        Returns (first step, steps run, seconds, losses as device scalars).
        """
        losses = []
        t = t0
        if traced_steps:
            while True:                          # run up to a sync first
                m, _ = self.step(t)
                t += 1
                if (t - 1) % self.log_every == 0:
                    float(m["loss"])
                    break
        start = time.perf_counter()
        first = t
        deadline = start + seconds
        span = _annotate("window") if traced_steps else contextlib.nullcontext()
        with span:
            while True:
                m, _ = self.step(t)
                losses.append(m["loss"])
                t += 1
                if (t - 1) % self.log_every == 0:
                    with _annotate("sync"):
                        float(m["loss"])
                    now = time.perf_counter()
                    if traced_steps and t - first >= traced_steps:
                        break
                    if not traced_steps and now >= deadline:
                        break
        elapsed = time.perf_counter() - start
        return first, t - first, elapsed, losses

    def free(self):
        """Drop the program's state and compiled step."""
        self.params = self.opt_state = self.compiled = self.gen = None
        self.step_fn = None
