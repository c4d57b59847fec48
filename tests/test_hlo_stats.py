"""HLO collective parser tests: scanned == unrolled after loop correction."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

# The parser must run against HLO produced with multiple host devices; spawn
# a subprocess so XLA_FLAGS apply before jax init.
SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.analysis.hlo import parse_collectives

    mesh = jax.make_mesh((4,), ("model",), axis_types=(AxisType.Auto,))
    W_SH = NamedSharding(mesh, P(None, "model"))
    R_SH = NamedSharding(mesh, P(None, None))

    def layer(x, w):
        y = jax.lax.with_sharding_constraint(x @ w, W_SH)
        return jax.lax.with_sharding_constraint(y @ w.T, R_SH)

    def scanned(x, ws):
        def body(c, w):
            return layer(c, w), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    def unrolled(x, ws):
        for i in range(ws.shape[0]):
            x = layer(x, ws[i])
        return x

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)
    out = {}
    with mesh:
        for name, fn in [("scanned", scanned), ("unrolled", unrolled)]:
            c = jax.jit(fn, in_shardings=(R_SH, None)).lower(x, ws).compile()
            st = parse_collectives(c.as_text(), 4)
            out[name] = {"total": st.total_moved_bytes,
                         "kinds": st.per_kind_bytes,
                         "loops": st.loop_multipliers}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def hlo_results():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert res.returncode == 0, res.stderr[-2000:]
    import json
    return json.loads(res.stdout.strip().splitlines()[-1])


class TestLoopCorrection:
    def test_scanned_matches_unrolled(self, hlo_results):
        s, u = hlo_results["scanned"], hlo_results["unrolled"]
        assert u["total"] > 0
        np.testing.assert_allclose(s["total"], u["total"], rtol=0.05)

    def test_trip_count_detected(self, hlo_results):
        loops = hlo_results["scanned"]["loops"]
        assert any(int(v) == 6 for v in loops.values()), loops

    def test_allreduce_volume_sane(self, hlo_results):
        # per layer: one AR of f32[64,64] = 16384B * 2*(3/4) = 24576B; 6 layers
        ar = hlo_results["unrolled"]["kinds"].get("all-reduce", 0)
        np.testing.assert_allclose(ar, 6 * 16384 * 1.5, rtol=0.05)
