"""Readings of a cell's correctness numbers for setting their limits:
the program's on each seed, and the control's, the plain reference in
the precision below the configuration's (fp8 for bfloat16), on the same
inputs.  Training cells also read the planted faults: half of the batch
left out of the reference's loss, and a state left unchanged.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 1,2,3 --seconds 10

Each seed runs the cell as `run.py` does, with a short window, in one
process so that set-up is paid from the compile cache.  One JSON line per
seed; nothing here runs in the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chip import harness  # noqa: E402
from chip import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell(bench, args.workload)
    bench_run.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = {}
        line = bench_run.run_cell(bench, cell, seed, args.seconds, 0,
                                  control=True, record=rec)
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "checks": line["checks"],
                          "control": rec["control"],
                          "metrics": line["metrics"],
                          "memory_peak_bytes":
                              line["device"]["memory_peak_bytes"]}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
