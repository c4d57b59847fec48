"""The main-path Pallas kernels compile for a TPU v5e chip at real widths.

Nothing runs: each kernel is lowered with ``interpret=False`` against one
chip of a *described* v5e topology (the TPU compiler is installed even
where no chip is attached) and must fit the chip's 16 GB of HBM and
contain the Mosaic kernel (``tpu_custom_call``).  Interpret-mode tests at
toy widths cannot see a block that does not fit VMEM, a padded operand
that does not fit HBM, or a tiling Mosaic refuses; this file can.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.coord_stats.kernel import (coord_stats_pallas,
                                              krum_scores_pallas)
from repro.kernels.flash_attn.kernel import flash_attn_pallas
from repro.kernels.gram.kernel import tree_gram_pallas
from repro.kernels.weighted_sum.kernel import weighted_sum_pallas

HBM_BYTES = 16e9                        # one v5e chip
W = 4                                   # workers of the chip smoke run
SMOLLM = get_config("smollm-360m")
EMBED = SMOLLM.vocab_size * SMOLLM.d_model          # tied embedding leaf


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache without the chip: keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    return compiled.as_text(), total


def _kernel_names(hlo: str) -> set[str]:
    """The instruction names of the Mosaic kernels, less their ``.N``: the
    names the profiler gives their op events, which the benchmark's
    kernel metrics match.  A kernel's type may be a tuple of outputs."""
    return {m.group(1) for m in re.finditer(
        r"%([\w\-]+?)(?:\.\d+)? = [^=]*? custom-call\(.*"
        r'custom_call_target="tpu_custom_call"', hlo)}


def test_tree_gram_whole_model_stack(one_chip):
    """The fused Gram over smollm-360m's whole (4, N) fp32 gradient
    stack: read in place, no 128-row worker padding in HBM."""
    n = SMOLLM.param_count()
    x = jax.ShapeDtypeStruct((W, n), jnp.float32, sharding=one_chip)
    hlo, total = _compile(lambda X: tree_gram_pallas(X, interpret=False), x)
    assert _kernel_names(hlo) == {"tree_gram"}
    assert total < HBM_BYTES
    assert total < 1.01 * W * n * 4     # ~the stack itself, nothing more


def test_weighted_sum_embedding_leaf(one_chip):
    """The combine on the worker-major (4, 49152*960) embedding leaf."""
    g = jax.ShapeDtypeStruct((W, EMBED), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((W,), jnp.float32, sharding=one_chip)
    hlo, total = _compile(
        lambda G, cc: weighted_sum_pallas(G, cc, interpret=False), g, c)
    assert _kernel_names(hlo) == {"weighted_sum"}
    assert total < HBM_BYTES
    assert total < 1.01 * (W + 1) * EMBED * 4   # input + output, no pads


def test_masked_coord_median_w16(one_chip):
    """The masked coordinate-wise median at W=16 on the embedding leaf."""
    g = jax.ShapeDtypeStruct((16, EMBED), jnp.float32, sharding=one_chip)
    m = jax.ShapeDtypeStruct((16,), jnp.float32, sharding=one_chip)
    hlo, total = _compile(
        lambda G, mask: coord_stats_pallas(G, mask, op="median", f=3,
                                           interpret=False), g, m)
    assert _kernel_names(hlo) == {"coord_stats_pallas"}
    assert total < HBM_BYTES


def test_selection_kernel_names(one_chip):
    """The unmasked median and the Krum scores keep their kernel names
    (``name=`` on each ``pallas_call``), whatever wraps them."""
    g = jax.ShapeDtypeStruct((W, 4096), jnp.float32, sharding=one_chip)
    d2 = jax.ShapeDtypeStruct((16, 16), jnp.float32, sharding=one_chip)
    hlo, _ = _compile(
        lambda G, D: (coord_stats_pallas(G, op="median", f=1),
                      krum_scores_pallas(D, f=3)), g, d2)
    assert _kernel_names(hlo) == {"coord_stats_pallas", "krum_scores_pallas"}


def test_flash_attention_fwd_bwd_cell_widths(one_chip):
    """The flash-attention forward, dK/dV and dQ kernels at the benchmark
    cells' widths (SmolLM: 15 heads, 5 K/V heads, d 64; seq 2048, bf16),
    under a vmap of the 4 workers as the train step calls them: Mosaic
    takes the shape-derived blocks, and the step holds no (S, S) array."""
    cfg = SMOLLM
    H, KV, D, S = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 2048
    q = jax.ShapeDtypeStruct((W, 1, H, S, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((W, 1, KV, S, D), jnp.bfloat16,
                              sharding=one_chip)

    def fwd_bwd(q, k, v, do):
        def one(q, k, v, do):
            o, vjp = jax.vjp(flash_attn_pallas, q, k, v)
            return o, vjp(do)
        return jax.vmap(one)(q, k, v, do)

    hlo, total = _compile(fwd_bwd, q, kv, kv, q)
    assert _kernel_names(hlo) == {"flash_attn_fwd", "flash_attn_bwd_dkv",
                                  "flash_attn_bwd_dq"}
    # bf16 q, k, v, do in and o, dq, dk, dv out: 2 x 2 (H + KV) heads
    io = 2 * 2 * 2 * W * (H + KV) * S * D
    # beside them only O(S) residuals (o, lse, di); the fp32 scores of one
    # head would be W * S * S * 4 = 67 MB, of all heads 1 GB
    assert total < 1.25 * io
