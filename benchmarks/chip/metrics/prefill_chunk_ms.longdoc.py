"""prefill_chunk_ms.longdoc: ``prefill_chunk_ms`` (mean device time of
one execution of the jitted prefill-chunk program) in
``deepseek-v2-lite.longdoc``, which reports no ``ttft_p90_ms``; there it
moves ``output_tokens_per_s``.  Layer: model step."""

from chipbench.harness import BENCH_DIR, load_module

read = load_module(BENCH_DIR / "metrics" / "prefill_chunk_ms.py",
                   "chipbench_metric_prefill_chunk_ms").read
