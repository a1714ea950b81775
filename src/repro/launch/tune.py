"""Measured autotuning CLI — fill the tuned-plan cache on the live host.

Times the modeled top-K candidate plans for a suite of shapes with
`repro.bench.timing.measure` (every iteration blocked, median over
repeats), records the winners as `repro.tune.TuneEntry`s, fits per-chip
calibration corrections from the measured/modeled ratios, and — with
``--update-cache`` — persists everything to the versioned JSON cache
that ``mm_config(plan_mode="tuned")`` consults.

Suites:

  fig5    — dense skew sweep (the paper's aspect-ratio axis), scaled to
            ``--total`` so interpret-mode Pallas on a CPU host stays
            tractable; shape classes are bucketed, so small
            representatives still answer their whole class.
  sparse  — block-sparse layouts at two densities on the same scale.
  decode  — the GEMV decode classes (m in {1, 4, 8} exact against a
            K = N = ``--total`` weight): candidate sets include the
            split-K family, so on chips where it wins (--chip ipu_gc200)
            the cached winners are measured split-K plans.

``--budget-s`` bounds wall time: at least one shape is always tuned,
and the loop stops at the first shape that would exceed the budget.

Usage::

  PYTHONPATH=src python -m repro.launch.tune --suite fig5 --budget-s 60 \
      --update-cache [--cache PATH] [--chip C] [--amp A]

After writing, the cache file is re-loaded and schema-validated — the
CI smoke step relies on that round-trip failing loudly.
"""

from __future__ import annotations

import argparse
import os
import time

from repro.core import config as mmcfg
from repro.sparse.layout import BlockSparseLayout
from repro.tune import calibrate
from repro.tune.cache import TuneCache
from repro.tune.runtime import default_cache_path
from repro.tune.shapeclass import decode_classes
from repro.tune.tuner import tune_dense, tune_sparse

SUITES = ("fig5", "sparse", "decode")

# The fig5 aspect-ratio axis, power-of-two so shape classes map to
# themselves (tuning representatives, not neighbors).
FIG5_RATIOS = (1.0 / 16, 1.0 / 4, 1.0, 4.0, 16.0)
SPARSE_DENSITIES = (0.25, 0.5)


def _fig5_shapes(total_side: int) -> list[tuple[int, int, int]]:
    total = total_side * total_side
    out = []
    for r in FIG5_RATIOS:
        m = max(1, int(round((total * r) ** 0.5)))
        k = max(1, int(round((total / r) ** 0.5)))
        out.append((m, k, total_side))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--suite", choices=SUITES, default="fig5",
                    help="which shape family to tune")
    ap.add_argument("--budget-s", type=float, default=60.0,
                    help="wall-clock budget; at least one shape always runs")
    ap.add_argument("--update-cache", action="store_true",
                    help="persist winners (and fitted corrections) to --cache")
    ap.add_argument("--cache", default=None, metavar="PATH",
                    help=f"cache file (default: {default_cache_path()})")
    ap.add_argument("--total", type=int, default=256,
                    help="problem scale: dense shapes hold m*k = total^2 "
                         "with n = total (keep small on CPU hosts — "
                         "interpret-mode Pallas is slow)")
    ap.add_argument("--top", type=int, default=4,
                    help="how many modeled candidates to time per shape")
    ap.add_argument("--dtype-bytes", type=int, default=2, choices=(2, 4),
                    help="element width to tune for (2 = bf16, 4 = f32); "
                         "part of the cache key — tune the width your "
                         "models actually run")
    ap.add_argument("--iters", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=2)
    mmcfg.add_cli_args(ap)
    args = ap.parse_args(argv)

    cache_path = args.cache or default_cache_path()
    cache = (TuneCache.load(cache_path) if os.path.exists(cache_path)
             else TuneCache())
    deadline = time.monotonic() + args.budget_s

    entries = []
    with mmcfg.scope_from_args(args):
        cfg = mmcfg.current()
        chip = cfg.chip_spec
        print(f"# tuning suite={args.suite} chip={chip.name} "
              f"amp={cfg.amp:g} total={args.total} top={args.top} "
              f"budget={args.budget_s:g}s -> {cache_path}")
        if args.suite == "fig5":
            work = [("dense", s) for s in _fig5_shapes(args.total)]
        elif args.suite == "decode":
            work = [("dense", cls.dims)
                    for cls in decode_classes(args.total, args.total)]
        else:
            work = [("sparse", d) for d in SPARSE_DENSITIES]
        for i, (kind, item) in enumerate(work):
            if i > 0 and time.monotonic() > deadline:
                print(f"# budget exhausted after {i}/{len(work)} shapes")
                break
            t0 = time.monotonic()
            if kind == "dense":
                m, k, n = item
                entry = tune_dense(m, k, n, dtype_bytes=args.dtype_bytes,
                                   top=args.top, iters=args.iters,
                                   repeats=args.repeats)
            else:
                layout = BlockSparseLayout.random(
                    args.total, args.total, (32, 128), item)
                entry = tune_sparse(layout, args.total,
                                    dtype_bytes=args.dtype_bytes,
                                    top=args.top, iters=args.iters,
                                    repeats=args.repeats)
            entries.append(entry)
            cache.put(entry)
            print(f"{entry.key},{entry.measured_us:.1f},"
                  f"sched={entry.schedule};"
                  f"plan={'x'.join(str(b) for b in entry.blocks)};"
                  f"agree={entry.agreement};speedup={entry.speedup:.3f} "
                  f"({time.monotonic() - t0:.1f}s)")

        # ---- calibration: fold measured/modeled ratios into corrections.
        chip_entries = [e for e in cache.entries.values()
                        if e.chip == chip.name]
        if chip_entries:
            corr = calibrate.fit_corrections(chip_entries, chip)
            cache.corrections[chip.name] = corr.to_json()
            gather = ("datasheet" if corr.sparse_gather_frac is None
                      else f"{corr.sparse_gather_frac:g}")
            if corr.accepted:
                corrected = calibrate.apply_corrections(chip, corr)
                print(f"# calibration {chip.name}: "
                      f"time_frac={corr.time_frac:g} "
                      f"sparse_gather_frac={gather} "
                      f"(n_dense={corr.n_dense} n_sparse={corr.n_sparse}) -> "
                      f"corrected peak "
                      f"{corrected.peak_bf16_flops / 1e12:.1f} "
                      f"TFLOP/s; absorb via hw.register_chip")
            else:
                # The quality gate (calibrate.MAX_LOG_SPREAD) tripped: the
                # fit is recorded in the cache for inspection, but
                # apply_corrections would refuse it — say so instead of
                # previewing a corrected spec.
                import math as _math
                print(f"# calibration {chip.name}: REJECTED "
                      f"(cross-shape spread "
                      f"{_math.exp(corr.log_spread):.2f}x > "
                      f"{_math.exp(calibrate.MAX_LOG_SPREAD):.0f}x, "
                      f"n_dense={corr.n_dense}); corrections recorded but "
                      f"not absorbable")

    agree = sum(1 for e in entries if e.agreement)
    print(f"# tuned {len(entries)} shape classes; "
          f"agreement {agree}/{len(entries)}")
    if args.update_cache:
        cache.save(cache_path)
        # Round-trip: re-load and schema-validate what we just wrote, so a
        # malformed cache fails here (and in the CI smoke), not at the
        # first tuned plan lookup.
        reloaded = TuneCache.load(cache_path)
        print(f"# wrote {cache_path} ({len(reloaded.entries)} entries, "
              f"schema ok)")
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    raise SystemExit(main())
