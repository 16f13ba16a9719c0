"""GPipe pipeline: schedule correctness on a real multi-device axis
(subprocess with 4 fake devices) + bubble accounting."""

import pathlib
import subprocess
import sys
import textwrap

from repro.parallel.pipeline import bubble_fraction

_REPO = pathlib.Path(__file__).resolve().parents[1]


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(4, 4) == 3 / 7
    assert bubble_fraction(2, 14) == 1 / 15


def test_gpipe_matches_sequential():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.parallel.pipeline import gpipe_apply

        mesh = make_mesh((4,), ("pod",))
        n_stages, n_micro, mb, d = 4, 6, 2, 8
        ws = jax.random.normal(jax.random.PRNGKey(0), (n_stages, d, d)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

        def stage(w, h):
            return jnp.tanh(h @ w)

        got = gpipe_apply(stage, ws, x, mesh=mesh, axis="pod")

        ref = x
        for s in range(n_stages):
            ref = jnp.tanh(ref @ ws[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=_REPO, timeout=600)
    assert "OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])
