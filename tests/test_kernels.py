"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Marked ``slow``: Pallas interpret mode is minutes-scale on CPU, so CI runs
this module in a separate non-blocking lane (the <2 min gating lane
deselects it with ``-m "not slow"``); the tier-1 command still runs it
locally."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

from repro.kernels.coord_stats import ref as cs_ref
from repro.kernels.coord_stats.kernel import coord_stats_pallas
from repro.kernels.flash_attn.kernel import flash_attn_pallas
from repro.kernels.flash_attn.ref import flash_attn_ref
from repro.kernels.gram.kernel import gram_pallas, tree_gram_pallas
from repro.kernels.gram.ref import gram_ref, tree_gram_chunk_ref
from repro.kernels.weighted_sum.kernel import weighted_sum_pallas
from repro.kernels.weighted_sum.ref import weighted_sum_ref

TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), dtype=dtype)


class TestGramKernel:
    @pytest.mark.parametrize("n,p", [(64, 3), (1000, 15), (4096, 16),
                                     (777, 32), (2048, 60)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, rng, n, p, dtype):
        G = _rand(rng, (n, p), dtype)
        got = gram_pallas(G, block_n=256, interpret=True)
        want = gram_ref(G)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                                   atol=2e-1 if dtype == jnp.bfloat16 else 1e-2)

    def test_block_size_invariance(self, rng):
        G = _rand(rng, (1500, 12), jnp.float32)
        a = gram_pallas(G, block_n=128, interpret=True)
        b = gram_pallas(G, block_n=512, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)

    def test_symmetry_and_psd(self, rng):
        G = _rand(rng, (512, 10), jnp.float32)
        K = np.asarray(gram_pallas(G, interpret=True))
        np.testing.assert_allclose(K, K.T, rtol=1e-5)
        assert np.linalg.eigvalsh(K).min() > -1e-3


class TestFusedTreeGramKernel:
    """The one-pass chunk-streamed tree Gram vs its jnp chunk oracle.

    Uses module-local generators (not the shared session ``rng``) so the
    pre-existing kernel sweeps keep their exact random streams."""

    @pytest.mark.parametrize("w,n", [(3, 700), (7, 2048), (16, 5000),
                                     (32, 1111)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_chunk_ref(self, w, n, dtype):
        rng = np.random.default_rng(w * 10_000 + n)
        X = _rand(rng, (w, n), dtype)
        got = tree_gram_pallas(X, block_n=256, interpret=True)
        want = tree_gram_chunk_ref(X, block_n=256)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want),
            rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
            atol=2e-1 if dtype == jnp.bfloat16 else 1e-2)

    @pytest.mark.parametrize("stride", [2, 4])
    def test_sketch_stride_matches_chunk_ref(self, stride):
        """Index-map chunk sampling == the jnp chunk subset, bit-for-bit
        plan: both sides consume the same chunk_schedule."""
        rng = np.random.default_rng(71 + stride)
        X = _rand(rng, (5, 9000), jnp.float32)
        got = tree_gram_pallas(X, sketch_stride=stride, block_n=512,
                               interpret=True)
        want = tree_gram_chunk_ref(X, sketch_stride=stride, block_n=512)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-3)

    def test_block_size_invariance_unsketched(self):
        rng = np.random.default_rng(73)
        X = _rand(rng, (6, 3000), jnp.float32)
        a = tree_gram_pallas(X, block_n=128, interpret=True)
        b = tree_gram_pallas(X, block_n=1024, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-3)

    def test_symmetry_and_psd(self):
        rng = np.random.default_rng(79)
        X = _rand(rng, (10, 1500), jnp.float32)
        K = np.asarray(tree_gram_pallas(X, interpret=True))
        np.testing.assert_allclose(K, K.T, rtol=1e-5)
        assert np.linalg.eigvalsh(K).min() > -1e-3


class TestWeightedSumKernel:
    @pytest.mark.parametrize("n,p", [(64, 3), (1000, 15), (4096, 32), (513, 7)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, rng, n, p, dtype):
        G = _rand(rng, (p, n), dtype)             # worker-major (W, n)
        c = _rand(rng, (p,), jnp.float32)
        got = weighted_sum_pallas(G, c, block_n=256, interpret=True)
        want = weighted_sum_ref(G, c)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=3e-2 if dtype == jnp.bfloat16 else 1e-5,
            atol=3e-2 if dtype == jnp.bfloat16 else 1e-5)


class TestCoordStatsKernel:
    @pytest.mark.parametrize("op", ["median", "trimmed_mean", "meamed", "phocas"])
    @pytest.mark.parametrize("p,n,f", [(5, 300, 1), (15, 1000, 3),
                                       (16, 512, 2), (8, 257, 1)])
    def test_matches_ref(self, rng, op, p, n, f):
        Gw = _rand(rng, (p, n), jnp.float32)
        got = coord_stats_pallas(Gw, op=op, f=f, block_n=256, interpret=True)
        want = {"median": lambda: cs_ref.median_ref(Gw),
                "trimmed_mean": lambda: cs_ref.trimmed_mean_ref(Gw, f),
                "meamed": lambda: cs_ref.meamed_ref(Gw, f),
                "phocas": lambda: cs_ref.phocas_ref(Gw, f)}[op]()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5, err_msg=f"{op}")

    def test_even_p_median(self, rng):
        Gw = _rand(rng, (6, 100), jnp.float32)
        got = coord_stats_pallas(Gw, op="median", interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.median(np.asarray(Gw), axis=0), rtol=1e-5)


class TestFlashAttnKernel:
    @pytest.mark.parametrize("b,h,sq,sk,d", [
        (1, 2, 128, 128, 64),     # square prefill
        (2, 1, 64, 64, 128),
        (1, 2, 1, 256, 64),       # decode: one query, long cache
        (1, 1, 100, 100, 64),     # non-multiple of block
        (1, 1, 37, 256, 64),      # chunked prefill tail
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_matches_ref(self, rng, b, h, sq, sk, d, dtype):
        q = _rand(rng, (b, h, sq, d), dtype)
        k = _rand(rng, (b, h, sk, d), dtype)
        v = _rand(rng, (b, h, sk, d), dtype)
        got = flash_attn_pallas(q, k, v, causal=True, block_q=32, block_k=32,
                                interpret=True)
        want = flash_attn_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=4e-2 if dtype == jnp.bfloat16 else 2e-4,
            atol=4e-2 if dtype == jnp.bfloat16 else 2e-4)

    @pytest.mark.parametrize("window", [16, 64])
    def test_sliding_window(self, rng, window):
        q = _rand(rng, (1, 2, 128, 64), jnp.float32)
        k = _rand(rng, (1, 2, 128, 64), jnp.float32)
        v = _rand(rng, (1, 2, 128, 64), jnp.float32)
        got = flash_attn_pallas(q, k, v, causal=True, window=window,
                                block_q=32, block_k=32, interpret=True)
        want = flash_attn_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_non_causal(self, rng):
        q = _rand(rng, (1, 1, 64, 64), jnp.float32)
        k = _rand(rng, (1, 1, 96, 64), jnp.float32)
        v = _rand(rng, (1, 1, 96, 64), jnp.float32)
        got = flash_attn_pallas(q, k, v, causal=False, block_q=32, block_k=32,
                                interpret=True)
        want = flash_attn_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_rows_sum_to_attention_of_ones(self, rng):
        """Value = ones => output rows must be exactly ones (softmax sums 1)."""
        q = _rand(rng, (1, 1, 64, 32), jnp.float32)
        k = _rand(rng, (1, 1, 64, 32), jnp.float32)
        v = jnp.ones((1, 1, 64, 32), jnp.float32)
        got = flash_attn_pallas(q, k, v, causal=True, block_q=16, block_k=16,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(got), 1.0, rtol=1e-5)
