"""Continuous-batching serve demo: a ragged request mix (staggered
arrivals, mixed prompt/output lengths) slot-filled through the
block-paged KV cache, next to the synchronous bucket engine serving the
same work.  Timings here are the CPU's; speed is measured on the chip
by `benchmarks/chip/run.py`.

    PYTHONPATH=src python examples/serve_demo.py
    PYTHONPATH=src python examples/serve_demo.py --arch mamba2-370m
        (non-attention mixers cannot page; falls back to ServeEngine)
"""

import argparse
import math
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.models import init_params
from repro.serve import PagedServeEngine, Request, ServeEngine


def make_requests(rng, vocab, n, max_len):
    """Mostly short chat turns, a few long generations, ragged arrivals."""
    reqs = []
    tick = 0
    for _ in range(n):
        tick += int(rng.poisson(1))
        s = int(rng.integers(6, 48))
        gen = int(rng.integers(40, 80)) if rng.random() < 0.25 \
            else int(rng.integers(4, 16))
        gen = min(gen, max_len - s)
        prompt = rng.integers(0, vocab, (s,)).astype(np.int32)
        reqs.append(Request(prompt=prompt, n_steps=gen, arrival=tick))
    return reqs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=ARCHS)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = make_requests(rng, cfg.vocab_size, args.requests, args.max_len)
    total = sum(r.n_steps for r in reqs)

    try:
        eng = PagedServeEngine(cfg, params, max_len=args.max_len,
                               max_batch=args.max_batch)
    except NotImplementedError as e:
        # mamba2 / MLA / hybrid mixers keep state the paged cache cannot
        # hold — serve them through the synchronous bucket engine
        print(f"arch={cfg.name}: not pageable ({e}); using ServeEngine")
        s_max = max(r.prompt.shape[0] for r in reqs)
        n_max = max(r.n_steps for r in reqs)
        eng = ServeEngine(cfg, params,
                          max_len=32 * math.ceil((s_max + n_max) / 32))
        t0 = time.perf_counter()
        # same run(trace) protocol as the paged engine below — one padded
        # bucket replay (batch = the whole trace)
        results, stats = eng.run(reqs, temperature=args.temperature,
                                 batch=len(reqs))
        dt = time.perf_counter() - t0
        print(f"{len(reqs)} requests, {total} requested tokens, "
              f"wall={dt:.2f}s -> {total / dt:.1f} tok/s (bucketed, "
              f"{stats['decode_steps']} decode steps)")
        for i, r in enumerate(results[:3]):
            print(f"req{i}: {r.tokens[:10].tolist()}")
        return

    t0 = time.perf_counter()
    results, stats = eng.run(reqs, temperature=args.temperature)
    dt = time.perf_counter() - t0
    print(f"arch={cfg.name}: {len(reqs)} ragged requests "
          f"({total} requested tokens) on {args.max_batch} slots, "
          f"page={eng.page}, pool={eng.cache.capacity} blocks")
    print(f"wall={dt:.2f}s -> {total / dt:.1f} tok/s  "
          f"({stats['decode_steps']} decode steps over {stats['ticks']} "
          f"ticks, peak occupancy {stats['occupancy_max']:.0%})")
    for i, r in enumerate(results[:3]):
        wait = r.admitted - r.arrival
        print(f"req{i}: prompt={r.prompt_len:3d} +{len(r.tokens):3d} tokens "
              f"arrived@{r.arrival} admitted@{r.admitted} "
              f"(+{wait} tick wait) => {r.tokens[:8].tolist()}")


if __name__ == "__main__":
    main()
