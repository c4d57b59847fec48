"""Plain float32 reference of the benchmarked training steps.

From the seed alone: the synthetic token stream, the weights, each
worker's loss and gradient, the in-graph attack, the robust aggregation
(the Flag Aggregator from its paper's IRLS, or the coordinate median) and
AdamW with the warmup-cosine schedule.  ``run`` follows the first steps
and returns what the benchmark compares: each step's loss, the per-leaf
norms of the first aggregated gradient, and the per-leaf norms of the
parameters' change after the last step.

``quant`` and ``fault`` turn the reference into the checks' control and
planted faults: ``quant="fp8"`` computes every matmul one precision step
below the configuration's bfloat16; ``fault="half_batch"`` takes each
worker's loss over the first half of its tokens only.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import model as reference_model

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# data: the synthetic Markov-chain token stream
# ---------------------------------------------------------------------------

def unigram_table(data: dict, vocab: int) -> np.ndarray | None:
    """The map from the chain's states to tokens, or None for the identity.

    ``unigram: "zipf"`` maps state k to token floor((V + 1)^(k / V)) - 1,
    so that a uniform state is a token of probability about
    1 / ((r + 1) ln(V + 1)) at rank r: Zipf's law, as in text.  Workers
    then share their frequent tokens and their gradients share a
    component, as in data-parallel training on text.  Made in float64 on
    the host: an int32 table that every program gathers from alike.
    """
    kind = data.get("unigram", "uniform")
    if kind == "uniform":
        return None
    if kind != "zipf":
        raise ValueError(f"unknown unigram {kind!r}")
    k = np.arange(vocab, dtype=np.float64)
    return (np.floor(np.exp(k / vocab * np.log(vocab + 1.0))) - 1).astype(
        np.int32)


def to_tokens(states, table):
    """Chain states -> tokens through ``unigram_table``'s map."""
    return states if table is None else jnp.asarray(table)[states]


def lm_stream(seed: int, step: int, traffic: dict, vocab: int):
    """Worker-major (W, B, S) tokens and next-token labels of one step.

    Each worker draws from its own key of ``fold_in(PRNGKey(seed), step)``:
    a uniform start state, then S successors, each one of ``branch``
    candidates ``((h(ctx) * j) mod vocab, j = 1..branch)`` with
    ``h(ctx) = (ctx * a + b) mod (2^31 - 1)`` in wrapping int32, where
    ``a`` and ``b`` are the first two draws of numpy's PCG64 at the task
    seed.  The states are the tokens, or are mapped to tokens by
    ``unigram_table``.
    """
    W, B, S = traffic["workers"], traffic["per_worker_batch"], traffic["seq"]
    data = traffic["data"]
    branch = data["branch"]
    rng = np.random.default_rng(data["task_seed"])
    a = jnp.int32(rng.integers(1, 2**31 - 1))
    b = jnp.int32(rng.integers(1, 2**31 - 1))
    mod = jnp.int32(2**31 - 1)
    mult = jnp.arange(1, branch + 1, dtype=jnp.int32)
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed),
                                               step), W)

    def worker(key):
        k_start, k_pick = jax.random.split(key)
        tok = jax.random.randint(k_start, (B,), 0, vocab)
        picks = jax.random.randint(k_pick, (B, S), 0, branch)

        def advance(tok, pick):
            h = (tok * a + b) % mod
            cand = (h[:, None] * mult[None, :]) % vocab
            return cand[jnp.arange(B), pick], tok

        last, seq = jax.lax.scan(advance, tok, picks.T)
        toks = jnp.concatenate([seq.T, last[:, None]], axis=1)
        return toks[:, :-1], toks[:, 1:]

    tokens, labels = zip(*[worker(keys[w]) for w in range(W)])
    table = unigram_table(data, vocab)
    return (to_tokens(jnp.stack(tokens), table),
            to_tokens(jnp.stack(labels), table))


# ---------------------------------------------------------------------------
# schedule, attack, optimizer
# ---------------------------------------------------------------------------

def learning_rate(traffic: dict, step: int) -> float:
    """Linear warmup over ``warmup`` steps, then cosine from lr to lr/10."""
    lr, total, warm = traffic["lr"], traffic["total_steps"], traffic["warmup"]
    w = min(max(step / max(warm, 1), 0.0), 1.0)
    s = min(max(max(step - warm, 0) / total, 0.0), 1.0)
    return w * lr * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * s)))


def attack(grads: list, traffic: dict) -> list:
    """The first ``byzantine`` workers send what the attack makes of their
    gradient."""
    f = traffic["byzantine"]
    if traffic["attack"] == "none" or f == 0:
        return grads
    if traffic["attack"] != "sign_flip":
        raise NotImplementedError(f"attack {traffic['attack']!r}")
    scale = traffic["attack_scale"]
    return ([jax.tree.map(lambda g: -scale * g, g) for g in grads[:f]]
            + grads[f:])


def adamw(p, mu, nu, d, count: int, lr: float, hp: dict):
    b1, b2, eps, wd = hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, d)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, d)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    p = jax.tree.map(
        lambda x, m, v: x - lr * (m / c1 / (jnp.sqrt(v / c2) + eps) + wd * x),
        p, mu, nu)
    return p, mu, nu


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

def gram(grads: list) -> jnp.ndarray:
    """K_ij = <g_i, g_j> over every coordinate, fp32."""
    W = len(grads)
    flat = [jax.tree.leaves(g) for g in grads]
    K = jnp.zeros((W, W), jnp.float32)
    for leaf in range(len(flat[0])):
        M = jnp.stack([f[leaf].reshape(-1) for f in flat])
        K = K + jnp.matmul(M, M.T, precision=HIGHEST)
    return K


def flag_weights(K: jnp.ndarray, fc: dict) -> jnp.ndarray:
    """Flag Aggregator weights c with d = sum_i c_i g_i (no regularizer).

    Algorithm 1 of the paper on the normalized gradients g~_i: IRLS of the
    sqrt(1 - v_i) loss, each round the top-m left singular subspace Y of
    [sqrt(u_i) g~_i]; stop after ``n_iter`` rounds or when the chordal
    distance between rounds falls under ``tol``.  The update is
    d = (1/p) Y Y^T G~ nu', nu' the worker norms capped at their median.
    The SVD of [sqrt(u_i) g~_i] is taken through its (p, p) Gram.
    """
    if fc["lam"] != 0.0:
        raise NotImplementedError("pairwise regularizer")
    p = K.shape[0]
    m, eps = fc["m"], fc["eps"]
    nu = jnp.sqrt(jnp.clip(jnp.diag(K), eps))
    Kt = K / (nu[:, None] * nu[None, :])
    Kt = Kt - jnp.diag(jnp.diag(Kt)) + jnp.eye(p)

    def subspace(u):
        su = jnp.sqrt(u)
        lam, V = jnp.linalg.eigh(su[:, None] * Kt * su[None, :])
        lam, V = lam[-m:], V[:, -m:]
        inv = jnp.where(lam > eps, 1.0 / jnp.maximum(lam, eps), 0.0)
        # Y = G~ diag(su) V lam^-1/2;  T maps G~-coordinates onto Y
        T = su[:, None] * V * jnp.sqrt(inv)[None, :]          # (p, m)
        return T

    def explained(T):
        Z = jnp.matmul(T.T, Kt, precision=HIGHEST)             # Y^T G~
        return jnp.clip(jnp.sum(Z * Z, axis=0), 0.0, 1.0)

    def irls(v):
        v = jnp.clip(v, 0.0, 1.0 - eps)
        w = 0.5 * jnp.clip(1.0 - v, eps, 1.0) ** -0.5
        return jnp.clip(w, 0.0, 1.0 / eps)

    T = subspace(jnp.ones((p,)))
    done = jnp.asarray(False)
    for _ in range(fc["n_iter"]):
        T_new = subspace(irls(explained(T)))
        overlap = jnp.matmul(jnp.matmul(T.T, Kt, precision=HIGHEST), T_new,
                             precision=HIGHEST)                # Y^T Y'
        chordal = 2.0 * (m - jnp.sum(overlap ** 2))
        T = jnp.where(done, T, T_new)        # the round that converges
        done = done | (chordal < fc["tol"])  # is the last one taken
    if fc["norm_mode"] != "clip":
        raise NotImplementedError(fc["norm_mode"])
    nu_eff = jnp.minimum(nu, jnp.median(nu))
    # d = (1/p) Y Y^T G~ nu'  ->  weights on G~, then on G
    ct = jnp.matmul(T, jnp.matmul(T.T, jnp.matmul(Kt, nu_eff,
                                                  precision=HIGHEST),
                                  precision=HIGHEST), precision=HIGHEST) / p
    return ct / nu


def aggregate(grads: list, traffic: dict):
    """The robust aggregate of the received worker gradients."""
    rule = traffic["aggregator"]
    if rule == "flag":
        c = flag_weights(gram(grads), traffic["flag"])
        return jax.tree.map(lambda *g: sum(c[i] * x for i, x in enumerate(g)),
                            *grads)
    if rule == "median":
        return jax.tree.map(lambda *g: jnp.median(jnp.stack(g), axis=0),
                            *grads)
    raise NotImplementedError(f"aggregator {rule!r}")


# ---------------------------------------------------------------------------
# the steps
# ---------------------------------------------------------------------------

def leaf_norms(tree) -> jnp.ndarray:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def run(config: dict, traffic: dict, seed: int, steps: int = 3, *,
        quant=None, fault=None) -> dict:
    """Follow ``steps`` training steps from the seed; see module doc."""
    if traffic["optimizer"] != "adamw":
        raise NotImplementedError(traffic["optimizer"])
    model = reference_model(config)
    W, S = traffic["workers"], traffic["seq"]
    mask = None
    if fault == "half_batch":
        mask = (jnp.arange(S) < S // 2).astype(jnp.float32)
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")

    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.value_and_grad(functools.partial(
            model.loss, c=config, quant=quant, loss_mask=mask)))

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def update(grads, p, mu, nu, lr, count):
            d = aggregate(attack(grads, traffic), traffic)
            p, mu, nu = adamw(p, mu, nu, d, count, lr, traffic["adamw"])
            return p, mu, nu, leaf_norms(d)

        p = jax.jit(model.init, static_argnums=1)(
            jax.random.PRNGKey(seed), _Hashable(config))
        mu = jax.tree.map(jnp.zeros_like, p)
        nu = jax.tree.map(jnp.zeros_like, p)
        losses, first_grad = [], None
        for t in range(steps):
            tokens, labels = lm_stream(seed, t, traffic, config["vocab_size"])
            grads, ls = [], []
            for w in range(W):
                lw, g = grad_fn(p, tokens[w], labels[w])
                ls.append(lw)
                grads.append(g)
            losses.append(float(sum(ls)) / W)
            p, mu, nu, dn = update(grads, p, mu, nu,
                                   learning_rate(traffic, t), float(t + 1))
            del grads
            if first_grad is None:
                first_grad = np.asarray(dn)
        del mu, nu
        p0 = jax.jit(model.init, static_argnums=1)(
            jax.random.PRNGKey(seed), _Hashable(config))
        change = jax.jit(lambda a, b: leaf_norms(
            jax.tree.map(jnp.subtract, a, b)))(p, p0)
    return {"losses": losses, "first_grad": first_grad,
            "change": np.asarray(change)}


class _Hashable(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))
