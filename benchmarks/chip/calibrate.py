"""Readings that the correctness limits are set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload qwen2-7b.chat \
        --seeds 101,102,103 --seconds 8

For each seed: the cell's own traffic at its own load, a short window,
the sample the benchmark draws, and on it both the program's widest
served-token gap and the control's (the reference from fp8 operands,
the next precision below the bf16 the configuration states).  One JSON
line per seed, then the largest program reading and the smallest
control reading.  The control's reading goes through the same verdict
as the program's; if it comes out correct on any seed, the limit does
not separate the two and the command exits 1.  The benchmark's own runs
never run the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from chipbench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = harness.resolve(bench, args.workload)
    harness.setup_compile_cache()
    devices = harness.require_chip(cell.chips)
    prog, ctl, ctl_correct = [], [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run(cell, seed, args.seconds, False, devices,
                          time.perf_counter(), control=True)
        g = out["checks"]["logit_gap"]["value"]
        prog.append(g)
        ctl.append(out["control_gap"])
        ctl_correct.append(out["control_correct"])
        print(json.dumps({"seed": seed, "program_gap": g,
                          "program_correct": out["correct"],
                          "control_gap": out["control_gap"],
                          "control_correct": out["control_correct"],
                          "failed": out["failed"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(prog),
                      "program_max": max(prog), "control_min": min(ctl),
                      "ratio": min(ctl) / max(max(prog), 1e-30),
                      "controls_correct": sum(ctl_correct)}))
    return 1 if any(ctl_correct) else 0


if __name__ == "__main__":
    sys.exit(main())
