"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, metrics and correctness limits
are found by name (see harness.py).  Set-up (process start to the
window's opening) is `setup_s`; the window then runs for `--seconds`.
With `--trace 0` the result holds the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of the
window.  Afterwards the program's output is compared with the
configuration's plain reference, and the numbers compared are printed
with their limits as the last lines of standard error and under
`checks`, the last key of the result line.

The last line of standard output is the result, one JSON object.  A run
that finds no TPU, or fewer chips than the cell asks for, exits non-zero
and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chip import harness, peaks, traffic, trace_reduce  # noqa: E402

EXIT_REFUSED = 3


class CompileCounter:
    """Executables compiled or loaded from the persistent cache, counted
    from JAX's monitoring events since `reset()`."""

    def __init__(self):
        import jax.monitoring as mon

        self.loads = 0
        self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.loads += 1

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def reset(self) -> "CompileCounter":
        self.loads = self.hits = 0
        return self

    def read(self) -> dict:
        return {"loaded_or_compiled": self.loads, "cache_hits": self.hits,
                "compiled": self.loads - self.hits}


class Context:
    """What a cell's driver needs: its entry, files, seed and the chip."""

    def __init__(self, cell, seed, seconds, trace, devices,
                 root=harness.HERE):
        self.cell, self.root = cell, root
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.config = harness.config(cell["config"], root)
        self.mix = traffic.load(cell["traffic"], root)
        self.limits = harness.limits(cell["name"], root)
        self.dims = harness.dims(self.config)
        self.devices = devices
        self.peaks = peaks.for_kind(devices[0].device_kind) \
            if devices[0].platform == "tpu" else None
        self._counter = CompileCounter()
        self._trace_root = None
        # read the control's numbers too (control.py); never in a run
        self.control = False

    def compile_counter(self) -> CompileCounter:
        return self._counter.reset()

    def start_trace(self):
        if not self.trace:
            return None
        import jax

        self._trace_root = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._trace_root, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        return self._trace_root

    def stop_trace(self, trace_dir):
        if not trace_dir:
            return None
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        return trace_reduce.find_xplane(trace_dir)

    def memory_peak(self) -> int:
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    def cleanup(self):
        if self._trace_root:
            shutil.rmtree(self._trace_root, ignore_errors=True)


def find_devices(chips: int, require_tpu: bool = True):
    import jax

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise harness.Refused(
                f"no TPU: JAX's first device is {devices[0].platform!r}")
        peaks.for_kind(devices[0].device_kind)      # unknown kind: refused
    if len(devices) < chips:
        raise harness.Refused(f"the cell needs {chips} chips, JAX sees "
                              f"{len(devices)}")
    return devices[:chips]


def reduce_trace(rec: dict, path: str, devices,
                 require_device: bool = True) -> None:
    """Add the trace's numbers, from the planes of the devices the cell
    uses, to the run record.  Without a device plane (a CPU rehearsal)
    nothing is added."""
    tr = trace_reduce.load(path)
    used = {f"/device:TPU:{d.id}" for d in devices}
    if used & set(tr.device):
        tr.device = {p: ev for p, ev in tr.device.items() if p in used}
    windows = [s for s in tr.host if s[0] == "bench.window"]
    if not windows:
        raise RuntimeError("the trace holds no window")
    if not tr.device:
        if require_device:
            raise RuntimeError("the trace holds no device operations")
        return
    _, a, b = windows[0]
    spans = [s for s in tr.host if a <= s[1] and s[2] <= b]
    steps = [s for s in spans if s[0].startswith(rec["step_span"])]
    merged = {d: trace_reduce.merge(ev) for d, ev in tr.device.items()}
    busy = {d: trace_reduce.busy_within(m, a, b) * 1e-9
            for d, m in merged.items()}
    busiest = max(busy, key=busy.get)
    collective = {d: trace_reduce.collective_ns(ev, a, b) * 1e-9
                  for d, ev in tr.device.items()}
    rec["trace"] = {
        "window_s": (b - a) * 1e-9,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_s_busiest": busy[busiest],
        "step_spans_s": [(e - s) * 1e-9 for _, s, e in steps],
        "step_busy_s": [x * 1e-9 for x in
                        trace_reduce.span_busy(merged[busiest], steps)],
        "collective_s_most": max(collective.values()),
    }
    rec["breakdown"] = {
        "device_ops": trace_reduce.top_ops(tr.device[busiest], a, b),
        "idle_gaps": trace_reduce.idle_gaps(merged[busiest], a, b, spans),
    }


def result_line(ctx, rec: dict, wanted) -> dict:
    metrics = {}
    for m in wanted:
        value = harness.reader(m["name"], ctx.root)(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = rec["checks"]
    correct = all(_passes(c) for c in checks.values())
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    if "trace" in rec:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if "breakdown" in rec:
        out["breakdown"] = rec["breakdown"]
    out["checks"] = checks
    return out


def _passes(c: dict) -> bool:
    v = c["value"]
    if v is None or (isinstance(v, float) and not math.isfinite(v)):
        return False
    return v <= c["limit"] if c["pass_if"] == "<=" else v >= c["limit"]


def run_cell(bench, cell, seed, seconds, trace, *, root=harness.HERE,
             require_tpu=True, control=False, record=None,
             mix_overrides=None) -> dict:
    """Set up, measure and check one cell; return its result line.  With
    `control` the record also holds the control's readings; `record`, a
    dict, receives the whole run record; `mix_overrides` replaces entries
    of the mix (the rate sweep)."""
    devices = find_devices(cell["chips"], require_tpu)
    ctx = Context(cell, seed, seconds, trace, devices, root)
    ctx.control = control
    ctx.mix.update(mix_overrides or {})
    if ctx.mix["kind"] == "serve":
        from chip import serve as driver
    else:
        from chip import train as driver
    try:
        rec = driver.run(ctx, t0=T0)
        if "trace_path" in rec:
            reduce_trace(rec, rec.pop("trace_path"), devices, require_tpu)
    finally:
        ctx.cleanup()
    wanted = (harness.per_layer(bench, cell["name"]) if trace
              else harness.end_to_end(bench, cell["name"]))
    line = result_line(ctx, rec, wanted)
    if record is not None:
        record.update(rec)
    for note in rec.get("notes", []):
        print(note, file=sys.stderr)
    return line


def enable_cache() -> None:
    """JAX's persistent compilation cache at its fixed path in the
    checkout (or `$JAX_COMPILATION_CACHE_DIR`), keeping every program
    however quickly it compiled, so that only a checkout's first run of a
    cell compiles."""
    import jax

    from repro.launch import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.cell(bench, args.workload)
    enable_cache()
    try:
        line = run_cell(bench, cell, args.seed, args.seconds, args.trace)
    except harness.Refused as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return EXIT_REFUSED
    except Exception:
        traceback.print_exc()
        return 1
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} {c['pass_if']} limit "
              f"{c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
