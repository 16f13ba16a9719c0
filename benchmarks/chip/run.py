"""The chip benchmark's command; see ``chipbench/harness.py``.

    python3 benchmarks/chip/run.py --workload qwen2-7b.chat --seed 1 \
        --seconds 30 --trace 0
"""

import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
