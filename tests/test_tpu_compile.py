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
    kernel metrics match."""
    return {m.group(1) for m in re.finditer(
        r"%([\w\-]+?)(?:\.\d+)? = \S+ custom-call\(.*"
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
