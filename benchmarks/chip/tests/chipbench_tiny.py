"""A tiny cell for the CPU tests: the qwen2-7b.chat cell's files with
every width cut down, prompts that still cross pages and chunks."""

import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from chipbench import harness  # noqa: E402

# widths cut, with initializer_range scaled by sqrt(3584 / 128) so that
# activations and logits keep the scale they have at qwen2-7b widths
TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
            head_dim=32, intermediate_size=256, vocab_size=512,
            num_hidden_layers=2, initializer_range=0.02 * (3584 / 128) ** 0.5,
            max_batch=4, max_len=1024,
            prefill_chunk=64, page=512, n_blocks=9)
TINY_MIX = dict(prompt={"dist": "lognormal", "median": 200, "sigma": 0.9,
                        "min": 8, "max": 700},
                output={"dist": "lognormal", "median": 40, "sigma": 0.7,
                        "min": 2, "max": 120},
                requests=64)
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_cell(cell: str = "qwen2-7b.chat"):
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    c = harness.resolve(bench, cell)
    c.conf = dict(c.conf, **TINY)
    c.mix = dict(c.mix, **TINY_MIX)
    c.limits = dict(c.limits, sample_tokens=48, sample_requests=4)
    return c


def tiny_run(cell, seed: int, seconds: float = 1.5, trace: bool = False,
             **kw):
    import jax
    return harness.run(cell, seed, seconds, trace, jax.devices(),
                       time.perf_counter(), peaks=PEAKS, pallas_device=None,
                       **kw)
