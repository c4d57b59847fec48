#!/usr/bin/env python3
"""Records the small chip trace that ``test_trace.py`` reads.

    python chipbench/tests/record_trace.py <out.xplane.pb>

On a TPU: the program's Pallas Gram (``tree_gram``), combine
(``weighted_sum``) and coordinate-median (``coord_stats_pallas``)
kernels on a (4, 2**20) fp32 stack, three times each,
inside a ``chipbench.window`` annotation, with a host ``chipbench.feed``
span before each round.  Copies the one ``.xplane.pb`` to the path given.
"""

import glob
import shutil
import sys
import tempfile
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT / "src"))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from repro.kernels.coord_stats.kernel import coord_stats_pallas
    from repro.kernels.gram.kernel import tree_gram_pallas
    from repro.kernels.weighted_sum.kernel import weighted_sum_pallas

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 2**20), jnp.float32)
    c = jnp.asarray([0.1, 0.2, 0.3, 0.4], jnp.float32)
    gram = jax.jit(tree_gram_pallas)
    wsum = jax.jit(weighted_sum_pallas)
    med = jax.jit(lambda g: coord_stats_pallas(g, op="median", f=1))
    jax.block_until_ready((gram(x), wsum(x, c), med(x)))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.feed"):
                y = x + 1.0
            jax.block_until_ready((gram(y), wsum(y, c), med(y)))
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    print(f"record_trace: wrote {out} ({Path(out).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
