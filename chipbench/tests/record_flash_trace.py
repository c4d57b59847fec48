#!/usr/bin/env python3
"""Records the small chip trace that ``test_flash_attn_ms.py`` reads.

    python chipbench/tests/record_flash_trace.py <out.xplane.pb>

On a TPU: the program's flash-attention kernels (``flash_attn_fwd``,
``flash_attn_bwd_dkv``, ``flash_attn_bwd_dq``) on one causal
grouped-query forward and backward, (1, 6 heads, 2 K/V heads, 1024, 64)
in bfloat16, three times, inside a ``chipbench.window`` annotation, with
a host ``chipbench.feed`` span before each round.  Copies the one
``.xplane.pb`` to the path given.
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

    from repro.kernels.flash_attn.kernel import flash_attn_pallas

    if jax.devices()[0].platform != "tpu":
        print("record_flash_trace: needs a TPU", file=sys.stderr)
        return 3
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, do = (jax.random.normal(kk, (1, 6, 1024, 64), jnp.bfloat16)
             for kk in keys[:2])
    k, v = (jax.random.normal(kk, (1, 2, 1024, 64), jnp.bfloat16)
            for kk in keys[2:])

    @jax.jit
    def fwd_bwd(q, k, v, do):
        o, vjp = jax.vjp(flash_attn_pallas, q, k, v)
        return o, vjp(do)

    jax.block_until_ready(fwd_bwd(q, k, v, do))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.feed"):
                q = q + 1.0
            jax.block_until_ready(fwd_bwd(q, k, v, do))
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    print(f"record_flash_trace: wrote {out} ({Path(out).stat().st_size} "
          "bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
