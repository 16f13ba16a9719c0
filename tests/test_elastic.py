"""Elastic scaling: a checkpoint saved on ONE device restores onto an
8-device production-style mesh with FSDP/TP shardings (subprocess with
fake devices) — the restart-on-different-cluster-size path."""

import pathlib
import subprocess
import sys
import textwrap

import jax

from repro.configs import get_config
from repro.models import init_params
from repro.train.checkpoint import save

_REPO = pathlib.Path(__file__).resolve().parents[1]


def test_save_one_device_restore_eight(tmp_path):
    cfg = get_config("qwen2-7b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    save(tmp_path, 42, params)

    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["REPRO_CPU_F32_DOTS"] = "1"
        import sys; sys.path.insert(0, "src")
        import jax, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import init_params
        from repro.models.model import param_axes_rule
        from repro.parallel.api import logical_to_spec
        from repro.train.checkpoint import restore

        cfg = get_config("qwen2-7b").reduced()
        mesh = make_mesh((4, 2), ("data", "model"))
        like = jax.eval_shape(lambda k: init_params(cfg, k),
                              jax.random.PRNGKey(0))

        # path-keyed shardings (the elastic-restore contract)
        specs = {{}}
        import jax.tree_util as jtu
        for path, leaf in jtu.tree_flatten_with_path(like)[0]:
            key = "::".join(str(p.key) if hasattr(p, "key") else
                            "#%d" % p.idx for p in path)
            specs[key] = NamedSharding(
                mesh, logical_to_spec(leaf.shape, param_axes_rule(path, leaf),
                                      mesh))

        restored, step = restore(r"{tmp_path}", like,
                                 sharding_fn=lambda k, a: specs[k])
        assert step == 42
        leaves = jax.tree.leaves(restored)
        # sharded across the 8 devices, and values intact
        assert any(len(l.sharding.device_set) == 8 for l in leaves)
        total = float(sum(np.abs(np.asarray(l, np.float32)).sum()
                          for l in leaves))
        assert np.isfinite(total) and total > 0
        print("OK", step, len(leaves))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=_REPO, timeout=600)
    assert "OK 42" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])
