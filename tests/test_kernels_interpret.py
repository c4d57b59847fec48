"""Tier-1 interpret-mode execution smoke for the production kernels.

One *tiny-shape* run per kernel family under the Pallas interpreter —
the dynamic twin of the static KTILING rule: an index map that reads out
of bounds at runtime fails here even if a rule regression ever let it
through statically.  The exhaustive allclose sweeps stay in the slow
lane (``tests/test_kernels.py``); these shapes are chosen to trace and
run in seconds so tier-1 always executes every kernel at least once.
"""

from __future__ import annotations

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


def test_weighted_sum_interpret(prng):
    G = jnp.asarray(prng.normal(size=(6, 500)), jnp.float32)  # (W, n)
    c = jnp.asarray(prng.normal(size=(6,)), jnp.float32)
    got = weighted_sum_pallas(G, c, block_n=256, interpret=True)
    np.testing.assert_allclose(got, weighted_sum_ref(G, c),
                               rtol=1e-5, atol=1e-5)
