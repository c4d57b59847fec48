"""Tier-1 interpret-mode execution smoke for the production kernels.

One *tiny-shape* run per kernel family under the Pallas interpreter —
the dynamic twin of the static KTILING rule: an index map that reads out
of bounds at runtime fails here even if a rule regression ever let it
through statically.  The exhaustive allclose sweeps stay in the slow
lane (``tests/test_kernels.py``); these shapes are chosen to trace and
run in seconds so tier-1 always executes every kernel at least once.
Flash attention, which training differentiates, also has its gradients
checked here against ``jax.grad`` of the fp32 reference, with the
dispatch rule of ``models.attention.attend`` and one tiny train step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.coord_stats.kernel import (bulyan_select_pallas,
                                              coord_stats_pallas,
                                              krum_scores_pallas)
from repro.kernels.coord_stats.ref import median_ref
from repro.kernels.flash_attn.kernel import flash_attn_pallas
from repro.kernels.flash_attn.ref import flash_attn_ref
from repro.kernels.gram.kernel import gram_pallas, tree_gram_pallas
from repro.kernels.gram.ref import gram_ref
from repro.kernels.weighted_sum.kernel import weighted_sum_pallas
from repro.kernels.weighted_sum.ref import weighted_sum_ref
from repro.models.config import ModelConfig


@pytest.fixture(scope="module")
def prng():
    return np.random.default_rng(11)


def test_gram_interpret(prng):
    G = jnp.asarray(prng.normal(size=(300, 6)), jnp.float32)
    got = gram_pallas(G, block_n=128, interpret=True)
    np.testing.assert_allclose(got, gram_ref(G), rtol=1e-5, atol=1e-5)


def test_tree_gram_interpret(prng):
    X = jnp.asarray(prng.normal(size=(6, 700)), jnp.float32)
    got = tree_gram_pallas(X, block_n=256, interpret=True)
    np.testing.assert_allclose(got, X @ X.T, rtol=1e-5, atol=1e-5)


def test_coord_stats_interpret(prng):
    Gw = jnp.asarray(prng.normal(size=(7, 500)), jnp.float32)
    got = coord_stats_pallas(Gw, op="median", f=1, block_n=256,
                             interpret=True)
    np.testing.assert_allclose(got, median_ref(Gw), rtol=1e-6, atol=1e-6)


def test_coord_stats_masked_interpret(prng):
    Gw = jnp.asarray(prng.normal(size=(7, 300)), jnp.float32)
    mask = jnp.asarray([1, 1, 0, 1, 1, 0, 1], jnp.float32)
    got = coord_stats_pallas(Gw, mask, op="median", f=1, block_n=256,
                             interpret=True)
    ref = median_ref(Gw[jnp.asarray([0, 1, 3, 4, 6])])
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


def test_krum_bulyan_interpret(prng):
    G = prng.normal(size=(9, 40))
    D2 = jnp.asarray(
        ((G[:, None, :] - G[None, :, :]) ** 2).sum(-1), jnp.float32)
    scores = krum_scores_pallas(D2, f=2, interpret=True)
    # reference: sum of the p-f-2 smallest off-diagonal distances per row
    k = 9 - 2 - 2
    srt = np.sort(np.asarray(D2) + np.diag([np.inf] * 9), axis=1)
    np.testing.assert_allclose(scores, srt[:, :k].sum(1), rtol=1e-5)
    picks = bulyan_select_pallas(D2, f=2, interpret=True)
    assert picks.shape == (max(9 - 4, 1),)
    assert len(set(np.asarray(picks).tolist())) == picks.shape[0]


def test_flash_attn_interpret(prng):
    q = jnp.asarray(prng.normal(size=(1, 2, 24, 16)), jnp.float32)
    k = jnp.asarray(prng.normal(size=(1, 2, 40, 16)), jnp.float32)
    v = jnp.asarray(prng.normal(size=(1, 2, 40, 16)), jnp.float32)
    got = flash_attn_pallas(q, k, v, causal=True, block_q=8, block_k=16,
                            interpret=True)
    ref = flash_attn_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_flash_attn_decode_bf16_interpret(prng):
    q = jnp.asarray(prng.normal(size=(2, 2, 1, 16)), jnp.bfloat16)
    k = jnp.asarray(prng.normal(size=(2, 2, 48, 16)), jnp.bfloat16)
    v = jnp.asarray(prng.normal(size=(2, 2, 48, 16)), jnp.bfloat16)
    got = flash_attn_pallas(q, k, v, causal=False, block_q=8, block_k=16,
                            interpret=True)
    assert got.dtype == jnp.bfloat16          # fp32 accumulator, cast out
    ref = flash_attn_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), causal=False)
    np.testing.assert_allclose(got.astype(jnp.float32), ref,
                               rtol=2e-2, atol=2e-2)


def _attention_ref(q, k, v, **kw):
    """fp32 attention with K/V repeated to the query heads."""
    g = q.shape[1] // k.shape[1]
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    return flash_attn_ref(f32[0], jnp.repeat(f32[1], g, 1),
                          jnp.repeat(f32[2], g, 1), **kw)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


# (b, h, kv, sq, sk, d, causal, window, block): GQA G=3 as SmolLM has
# (15 heads, 5 K/V heads, d 64), MHA, windows, and lengths that are not a
# multiple of the block (q and k padded, the last block masked).
FLASH_GRAD_CASES = {
    "gqa3_causal": (1, 15, 5, 64, 64, 64, True, None, 16),
    "mha_causal": (2, 2, 2, 48, 48, 16, True, None, 16),
    "gqa2_window": (1, 4, 2, 64, 64, 16, True, 24, 16),
    "mha_noncausal": (1, 2, 2, 32, 48, 16, False, None, 16),
    "ragged_tail": (1, 6, 2, 37, 53, 16, True, None, 16),
    "prefill_tail": (1, 2, 1, 20, 64, 16, True, 16, 16),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("case", sorted(FLASH_GRAD_CASES))
def test_flash_attn_grad_interpret(case, dtype):
    """Output and d/dq, d/dk, d/dv of the kernels (custom_vjp) against
    ``jax.grad`` of the fp32 reference."""
    b, h, kv, sq, sk, d, causal, window, blk = FLASH_GRAD_CASES[case]
    r = np.random.default_rng(sq * h + sk)
    q, k, v = (jnp.asarray(r.normal(size=s), dtype)
               for s in ((b, h, sq, d), (b, kv, sk, d), (b, kv, sk, d)))
    do = jnp.asarray(r.normal(size=(b, h, sq, d)), jnp.float32)
    kw = dict(causal=causal, window=window)

    def kernel(q, k, v):
        return flash_attn_pallas(q, k, v, block_q=blk, block_k=blk,
                                 interpret=True, **kw).astype(jnp.float32)

    o, vjp = jax.vjp(kernel, q, k, v)
    o_ref, vjp_ref = jax.vjp(lambda *a: _attention_ref(*a, **kw), q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert np.isfinite(np.asarray(o)).all()
    assert _rel(o, o_ref) < tol
    for name, got, want in zip("qkv", vjp(do), vjp_ref(do)):
        assert got.dtype == dtype and got.shape == want.shape, name
        assert np.isfinite(np.asarray(got, np.float32)).all(), name
        assert _rel(got, want) < tol, name


def test_flash_attn_grad_under_worker_vmap():
    """The train step calls attention under ``jax.vmap`` over workers."""
    r = np.random.default_rng(3)
    W, shape_q, shape_kv = 3, (1, 6, 48, 16), (1, 2, 48, 16)
    q = jnp.asarray(r.normal(size=(W,) + shape_q), jnp.float32)
    k, v = (jnp.asarray(r.normal(size=(W,) + shape_kv), jnp.float32)
            for _ in range(2))

    def loss(attn, q, k, v):
        o = jax.vmap(attn)(q, k, v)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    got = jax.grad(lambda *a: loss(lambda q, k, v: flash_attn_pallas(
        q, k, v, block_q=16, block_k=16, interpret=True), *a),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: loss(_attention_ref, *a),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_flash_attn_masked_blocks_contribute_zero():
    """Key blocks outside every query's window are skipped: NaN there
    reaches neither the output nor any gradient, and their dK and dV are
    exactly zero."""
    r = np.random.default_rng(4)
    sq, sk, window, blk = 16, 64, 16, 16     # queries at 48..63 see 33..63
    q = jnp.asarray(r.normal(size=(1, 2, sq, 16)), jnp.float32)
    k, v = (jnp.asarray(r.normal(size=(1, 1, sk, 16)), jnp.float32)
            for _ in range(2))
    dead = jnp.arange(sk)[None, None, :, None] < 32      # key blocks 0, 1
    kn, vn = (jnp.where(dead, jnp.nan, x) for x in (k, v))

    def kernel(q, k, v):
        return flash_attn_pallas(q, k, v, window=window, block_q=blk,
                                 block_k=blk, interpret=True)

    o, vjp = jax.vjp(kernel, q, kn, vn)
    dq, dk, dv = vjp(jnp.ones_like(o))
    for x in (o, dq, dk, dv):
        assert np.isfinite(np.asarray(x)).all()
    assert not np.asarray(dk[:, :, :32]).any()
    assert not np.asarray(dv[:, :, :32]).any()
    np.testing.assert_allclose(o, _attention_ref(q, k, v, window=window),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tpu,mesh_size,ragged,kernel", [
    (False, None, False, False),     # CPU tests, host dry-run
    (True, None, False, True),       # one TPU device
    (True, 1, False, True),          # a one-device mesh
    (True, 4, False, False),         # GSPMD cannot partition the kernel
    (True, None, True, False),       # ragged cache
])
def test_attend_dispatch(monkeypatch, tpu, mesh_size, ragged, kernel):
    """``attend(impl="pallas")`` takes the kernels only on a TPU, with no
    mesh of several devices and no ``kv_valid`` mask; else ``xla_flash``."""
    import types

    from repro.models import attention

    monkeypatch.setattr(attention, "on_tpu", lambda: tpu)
    monkeypatch.setattr(attention, "current_mesh", lambda: None if (
        mesh_size is None) else types.SimpleNamespace(size=mesh_size))
    q = jnp.zeros((1, 6, 32, 16), jnp.bfloat16)
    k = jnp.zeros((1, 2, 32, 16), jnp.bfloat16)
    valid = jnp.ones((32,), bool) if ragged else None
    jaxpr = str(jax.make_jaxpr(lambda q, k: attention.attend(
        q, k, k, impl="pallas", kv_valid=valid))(q, k))
    assert ("pallas_call" in jaxpr) == kernel


def test_train_step_pallas_attention_matches_xla():
    """A tiny train step with ``attn_impl="pallas_interpret"`` gives the
    loss and aggregated gradient of ``attn_impl="xla"``."""
    from repro.dist.aggregation import AggregatorConfig
    from repro.dist.train_step import (TrainConfig, build_train_step,
                                       init_train_state)
    from repro.optim import constant, sgd

    cfg = ModelConfig(name="tiny-attn", arch_type="dense", num_layers=2,
                      d_model=64, num_heads=6, num_kv_heads=2, d_ff=128,
                      vocab_size=128, compute_dtype="float32")
    W, B, S = 3, 1, 32
    opt = sgd(momentum=0.0)
    params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    r = np.random.default_rng(5)
    batch = {n: jnp.asarray(r.integers(0, 128, size=(W, B, S)), jnp.int32)
             for n in ("tokens", "labels")}
    out = {}
    for impl in ("xla", "pallas_interpret"):
        tc = TrainConfig(aggregator=AggregatorConfig(name="mean"),
                         attn_impl=impl)
        step = jax.jit(build_train_step(cfg, tc, opt, constant(1.0)))
        new, _, m = step(params, opt_state, batch, jax.random.PRNGKey(1),
                         jnp.asarray(0, jnp.int32))
        # lr 1, no momentum: the parameters' change is the aggregate
        out[impl] = (float(m["loss"]),
                     jax.tree.map(jnp.subtract, params, new))
    (loss_x, d_x), (loss_p, d_p) = out["xla"], out["pallas_interpret"]
    assert loss_p == pytest.approx(loss_x, rel=1e-5)
    for a, b in zip(jax.tree.leaves(d_p), jax.tree.leaves(d_x)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


def test_weighted_sum_interpret(prng):
    G = jnp.asarray(prng.normal(size=(6, 500)), jnp.float32)  # (W, n)
    c = jnp.asarray(prng.normal(size=(6,)), jnp.float32)
    got = weighted_sum_pallas(G, c, block_n=256, interpret=True)
    np.testing.assert_allclose(got, weighted_sum_ref(G, c),
                               rtol=1e-5, atol=1e-5)
