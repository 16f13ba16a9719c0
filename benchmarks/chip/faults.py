"""Faults planted in the timed path, and whether the check sees them.

    python3 benchmarks/chip/faults.py --workload deepseek-v2-lite.longdoc \
        --faults kernel-page0,kernel-zero,experts-skipped,held-offset \
        --seeds 101 --seconds 8

For each fault and seed: the cell's own run (``harness.run``) with the
fault planted after the warm-up through its ``breaker``, so the broken
programs compile inside the run, and the verdict on the served tokens.
One JSON line each; the command exits 1 if any faulted run comes out
correct, since the cell's limit would then let that fault through.

The faults, each in the program a cell of the latent-attention,
routed-expert family runs:

* ``kernel-page0``: the paged decode kernel's latent mode reads each
  slot's first page in place of every page of its table;
* ``kernel-zero``: the latent mode returns zeros;
* ``experts-skipped``: no row reaches a held expert (the shared experts
  still run);
* ``held-offset``: the held experts take the assignments of the next
  share's experts (``held_offset`` off by the share's width).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from typing import Callable, Dict, List  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench import harness  # noqa: E402


def _patch(undo: List[Callable], mod, name: str, wrap: Callable) -> None:
    orig = getattr(mod, name)
    setattr(mod, name, wrap(orig))
    undo.append(lambda: setattr(mod, name, orig))


def _rebuild(eng, cfg=None, *, decode_only: bool = False) -> None:
    """Fresh step programs, traced at their first call under the patches
    in force then."""
    from repro.serve.paged_engine import serve_steps
    dec, pre = serve_steps(cfg or eng.cfg,
                           aligned=eng.page % eng.prefill_chunk == 0)
    eng._decode = dec
    if not decode_only:
        eng._prefill = pre


def _latent_kernel(change: Callable):
    """A breaker that passes the latent mode's calls through ``change``
    (tables, call) -> output."""
    def plant(eng, undo):
        from repro.kernels import ops

        def wrap(orig):
            def call(q, k_pool, v_pool, tables, *a, **kw):
                if v_pool is not None:
                    return orig(q, k_pool, v_pool, tables, *a, **kw)
                return change(tables, lambda t: orig(q, k_pool, v_pool, t,
                                                     *a, **kw))
            return call
        _patch(undo, ops, "paged_decode_attention", wrap)
        _rebuild(eng, decode_only=True)
    return plant


def _first_page(tables, call):
    import jax.numpy as jnp
    return call(jnp.broadcast_to(tables[:, :1], tables.shape))


def _zeros(tables, call):
    import jax.numpy as jnp
    return jnp.zeros_like(call(tables))


def _experts_skipped(eng, undo):
    import jax.numpy as jnp
    from repro.models import blocks

    def wrap(orig):
        def serve(cfg, w, x, valid=None):
            return orig(cfg, w, x, jnp.zeros(x.shape[:2], bool))
        return serve
    _patch(undo, blocks, "moe_serve", wrap)
    _rebuild(eng)


def _held_offset(eng, undo):
    m = eng.cfg.moe
    moe = dataclasses.replace(
        m, held_offset=(m.held_offset + m.held) % m.n_experts)
    _rebuild(eng, dataclasses.replace(eng.cfg, moe=moe))


FAULTS: Dict[str, Callable] = {
    "kernel-page0": _latent_kernel(_first_page),
    "kernel-zero": _latent_kernel(_zeros),
    "experts-skipped": _experts_skipped,
    "held-offset": _held_offset,
}


def run_faulted(cell, fault: str, seed: int, seconds: float, devices,
                **kw) -> Dict:
    """``harness.run`` with ``fault`` planted; the patches are undone
    afterwards."""
    undo: List[Callable] = []
    try:
        return harness.run(cell, seed, seconds, False, devices,
                           time.perf_counter(),
                           breaker=lambda eng: FAULTS[fault](eng, undo),
                           **kw)
    finally:
        for u in reversed(undo):
            u()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve(bench, args.workload)
    harness.setup_compile_cache()
    devices = harness.require_chip(cell.chips)
    seen = []
    for fault in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            out = run_faulted(cell, fault, seed, args.seconds, devices)
            g = out["checks"]["logit_gap"]
            seen.append(not out["correct"])
            print(json.dumps({"fault": fault, "seed": seed,
                              "gap": g["value"], "limit": g["limit"],
                              "correct": out["correct"],
                              "failed": out["failed"]}), flush=True)
    print(json.dumps({"workload": args.workload, "runs": len(seen),
                      "caught": sum(seen)}))
    return 0 if all(seen) else 1


if __name__ == "__main__":
    sys.exit(main())
