"""Trace explorer — run one traced workload, print the span tree.

  PYTHONPATH=src python -m repro.launch.trace --mode matmul --skew 64
  PYTHONPATH=src python -m repro.launch.trace --mode serve --out t.json
  PYTHONPATH=src python -m repro.launch.trace --mode serve --profile DIR

Arms `repro.obs.trace_scope` around a small real workload and shows
what the instrumented stack emits: the deterministic text tree on
stdout, the Chrome-trace JSON at ``--out`` (load it in Perfetto /
chrome://tracing).  The sim clock measures every dispatch at exactly
its modeled time, so the trace is host-independent and the drift report
comes back identically zero.  ``--profile DIR`` instead arms the
profiler sink inside a `jax.profiler` trace written under DIR: the
spans land beside the device's ops on one clock (open it in TensorBoard
or Perfetto), and the GC and JAX compile-stage totals are printed.

``--check`` turns the run into a smoke gate (CI's trace-smoke job):
the Chrome document must schema-validate, its event count must equal
the span-tree total, and every dispatch span must carry the attribution
fields (ladder rung, modeled_us, measured_us — plus the tune cache key
under ``--mm-plan-mode tuned``).  Exits non-zero on any violation.
"""

from __future__ import annotations

import argparse
import contextlib

import jax
import jax.numpy as jnp

from repro.core import config as mmcfg
from repro.obs import (
    REGISTRY,
    SimClock,
    drift_report,
    to_chrome,
    trace_scope,
    validate_chrome,
)
from repro.obs.spans import SINK_HISTOGRAMS


@contextlib.contextmanager
def _traced(args):
    """The span tree on the sim clock, or with ``--profile`` the
    profiler sink inside a profiler trace written there."""
    if not args.profile:
        with trace_scope(clock=SimClock()) as tr:
            yield tr
        return
    with jax.profiler.trace(args.profile), trace_scope(profiler=True) as tr:
        yield tr


def run_matmul(args):
    """A handful of skewed dense dispatches through `skewmm.matmul`."""
    from repro.core import skewmm

    k = args.size
    shapes = [
        (args.size, k, args.size),          # squared
        (args.size * args.skew, k, args.size),  # left-skewed
        (args.size, k, args.size * args.skew),  # right-skewed
        (1, k, args.size),                  # decode GEMV row
    ]
    with _traced(args) as tr:
        for m, kk, n in shapes:
            a = jnp.ones((m, kk), jnp.float32)
            b = jnp.ones((kk, n), jnp.float32)
            skewmm.matmul(a, b).block_until_ready()
    return tr


def run_serve(args):
    """A tiny scripted serve run under plan_mode=tuned (the obs-suite
    workload): cache built outside the scope, scheduler inside."""
    from repro.configs.base import get_config
    from repro.models.model import build_model
    from repro.serve.sched import (
        BucketTable,
        Scheduler,
        assert_covered,
        build_tuned_cache,
        capture_gemm_specs,
        scripted_trace,
    )
    from repro.tune import runtime as tune_runtime

    cfg = get_config(args.arch).reduced()
    table = BucketTable.for_workload(max_batch=2, max_prompt=8, max_new=2)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    specs = capture_gemm_specs(params, cfg, table)
    cache = build_tuned_cache(params, cfg, table)
    assert_covered(cache, specs)
    reqs = scripted_trace(
        [(0, 3, 2), (1, 5, 1), (2, 7, 2)], vocab_size=cfg.vocab_size, seed=3
    )
    with tune_runtime.use_cache(cache), mmcfg.mm_config(plan_mode="tuned"):
        with _traced(args) as tr:
            sched = Scheduler(params, cfg, table)
            results = sched.run(reqs, max_ticks=50)
    if len(results) != len(reqs):
        raise SystemExit(
            f"serve run incomplete: {len(results)}/{len(reqs)} requests"
        )
    return tr


def check_trace(tr, *, tuned: bool) -> list[str]:
    """The trace-smoke contract; returns human-readable violations."""
    problems = []
    doc = to_chrome(tr)
    try:
        validate_chrome(doc)
    except ValueError as e:
        problems.append(f"chrome schema: {e}")
    digest = tr.digest()
    n_events = len(doc["traceEvents"])
    if n_events != digest["total"]:
        problems.append(
            f"chrome event count {n_events} != span total {digest['total']}"
        )
    dispatches = [sp for sp in tr.spans() if sp.kind == "dispatch"]
    if not dispatches:
        problems.append("no dispatch spans emitted")
    for sp in dispatches:
        missing = []
        if "rung" not in sp.attrs:
            missing.append("rung")
        if tuned and "tune_key" not in sp.attrs:
            missing.append("tune_key")
        if sp.modeled_us is None:
            missing.append("modeled_us")
        if sp.measured_us is None:
            missing.append("measured_us")
        if missing:
            problems.append(
                f"dispatch span {sp.name!r} missing {missing} "
                f"(attrs: {sorted(sp.attrs)})"
            )
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("matmul", "serve"), default="matmul")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="arm the profiler sink and write a profiler "
                         "trace under DIR instead of the span tree")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the Chrome-trace JSON here")
    ap.add_argument("--size", type=int, default=128,
                    help="matmul mode: base dimension")
    ap.add_argument("--skew", type=int, default=8,
                    help="matmul mode: skew ratio for the long sides")
    ap.add_argument("--arch", default="phi4-mini-3.8b",
                    help="serve mode: model config (reduced)")
    ap.add_argument("--check", action="store_true",
                    help="validate the trace-smoke contract (chrome "
                         "schema, event counts, dispatch attribution) "
                         "and exit non-zero on violations")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the span-tree dump")
    mmcfg.add_cli_args(ap)
    args = ap.parse_args(argv)
    if args.profile and (args.out or args.check):
        ap.error("--profile writes no span tree: drop --out / --check")

    with mmcfg.scope_from_args(args):
        tuned = args.mode == "serve" or mmcfg.resolve().plan_mode == "tuned"
        tr = run_matmul(args) if args.mode == "matmul" else run_serve(args)

    if args.profile:
        hists = REGISTRY.histograms()
        for name in SINK_HISTOGRAMS:
            h = hists.get(name)
            if h is not None:
                print(f"[trace] {name}: {h.total():.1f} over {h.count()}")
        print(f"[trace] profile written under {args.profile}")
        return 0
    if not args.quiet:
        print(tr.render().rstrip("\n"))
    digest = tr.digest()
    print("[trace] " + "/".join(f"{k}:{v}" for k, v in sorted(digest.items())))
    drift = drift_report()
    print(f"[trace] drift: classes={drift['classes_total']} "
          f"max_abs_log={drift['max_abs_log']:.4f} "
          f"accepted={drift['accepted']}")
    if args.out:
        tr.export_chrome(args.out)
        print(f"[trace] wrote {args.out}")

    if args.check:
        problems = check_trace(tr, tuned=tuned)
        if problems:
            for p in problems:
                print(f"[trace] CHECK FAIL: {p}")
            return 1
        print("[trace] check ok")
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    raise SystemExit(main())
