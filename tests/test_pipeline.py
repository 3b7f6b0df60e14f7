"""GPipe pipeline-parallel module: correctness vs sequential execution
(4-stage pipe mesh in a subprocess) + schedule math."""

import subprocess
import sys
import textwrap

import pytest

from repro.sharding.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(4, 28) == pytest.approx(3 / 31)


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.sharding.pipeline import pipeline_forward
    from repro.launch.mesh import make_auto_mesh

    L, D, B, M = 8, 16, 12, 6
    key = jax.random.PRNGKey(0)
    w = 0.3 * jax.random.normal(key, (L, D, D), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D), jnp.float32)

    def layer(wi, h):
        return jnp.tanh(h @ wi)

    def stage_fn(ws, h):           # ws: [L/S, D, D]
        def body(h, wi):
            return layer(wi, h), None
        h, _ = jax.lax.scan(body, h, ws)
        return h

    # sequential reference
    ref = x
    for i in range(L):
        ref = layer(w[i], ref)

    mesh = make_auto_mesh((4,), ("pipe",))
    run = pipeline_forward(stage_fn, mesh, axis="pipe", n_micro=M)
    out = jax.jit(run)(w, x)
    err = float(jnp.max(jnp.abs(out - ref)))
    print("PIPEERR", err)
    assert err < 1e-5, err
    print("PIPEOK")
""")


@pytest.mark.slow
def test_pipeline_matches_sequential():
    res = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         cwd="/root/repo")
    assert res.returncode == 0, res.stderr[-2000:]
    assert "PIPEOK" in res.stdout, res.stdout
