"""decode_step_ms.longdoc: ``decode_step_ms`` (mean device time of one
execution of the jitted decode program) in ``deepseek-v2-lite.longdoc``,
which reports no ``itl_p95_ms``; there it moves ``output_tokens_per_s``.
Layer: model step."""

from chipbench.harness import BENCH_DIR, load_module

read = load_module(BENCH_DIR / "metrics" / "decode_step_ms.py",
                   "chipbench_metric_decode_step_ms").read
