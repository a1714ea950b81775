"""Find the highest open-loop rate a serving cell sustains: the cell's
mix run at each given rate, in one process, with a window of `--seconds`
each.  A rate is sustained when requests do not pile up waiting for
admission by the window's close.

    python3 benchmarks/chip/sweep.py --workload <cell> \
        --rates 0.2,0.4,0.6 --seconds 30 --seed 5

One JSON line per rate; the benchmark's own runs never sweep.
"""

from __future__ import annotations

import argparse
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell(bench, args.workload)
    bench_run.enable_cache()
    for rate in (float(r) for r in args.rates.split(",")):
        rec = {}
        line = bench_run.run_cell(bench, cell, args.seed, args.seconds, 0,
                                  record=rec,
                                  mix_overrides={"rate_per_s": rate})
        done = sum(1 for s in rec["steps"] if s["admit"])
        print(json.dumps({
            "rate_per_s": rate, "sent": rec["attempted"],
            "admitting_ticks": done, "ttft_samples": len(rec["ttft_s"]),
            "queued_at_close": rec["queued_at_close"],
            "out_tokens_per_s": rec["out_tokens"] / rec["window_s"],
            "metrics": line["metrics"], "correct": line["correct"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
