"""repro.obs: span tree, metrics registry, attribution, exporters."""

import json
import math
import threading

import jax.numpy as jnp
import pytest

from repro import guard
from repro.bench.record import BenchResult, Provenance
from repro.core import skewmm
from repro.core.config import mm_config
from repro.guard import health
from repro.kernels import ops
from repro.obs import (
    NULL_SPAN,
    REGISTRY,
    Registry,
    SimClock,
    annotate,
    current_span,
    current_trace,
    drift_report,
    event,
    export_chrome,
    percentile_nearest_rank,
    render_text,
    span,
    to_chrome,
    trace_scope,
    tracing,
    validate_chrome,
)
from repro.obs import spans as obs_spans
from repro.serve.sched.telemetry import ServeTelemetry, percentile
from repro.tune.calibrate import MAX_LOG_SPREAD


@pytest.fixture(autouse=True)
def _clean_state():
    guard.reset()
    yield
    guard.reset()


def _mats(m=8, k=256, n=512):
    a = jnp.ones((m, k), jnp.float32)
    b = jnp.ones((k, n), jnp.float32)
    return a, b


# ------------------------------------------------------------ span tree
class TestSpans:
    def test_disarmed_is_null(self):
        assert not tracing()
        assert current_trace() is None
        assert current_span() is None
        with span("dispatch", "x") as sp:
            assert sp is NULL_SPAN
        assert event("plan", "y") is NULL_SPAN
        assert annotate("dispatch", foo=1) is False
        # NULL_SPAN absorbs mutation without branching at call sites.
        assert NULL_SPAN.set(a=1) is NULL_SPAN

    def test_tree_structure_and_restore(self):
        with trace_scope() as tr:
            assert tracing()
            with span("tick", "t0") as t:
                event("plan", "p", m=4)
                with span("decode") as d:
                    assert current_span() is d
                assert current_span() is t
        assert not tracing()
        assert len(tr.roots) == 1
        root = tr.roots[0]
        assert [c.kind for c in root.children] == ["plan", "decode"]
        assert tr.digest() == {"decode": 1, "plan": 1, "tick": 1, "total": 3}

    def test_nested_scopes_innermost_wins(self):
        with trace_scope() as outer:
            event("plan", "outer")
            with trace_scope() as inner:
                event("plan", "inner")
                assert current_trace() is inner
            assert current_trace() is outer
            event("plan", "outer2")
        assert [s.name for s in outer.spans()] == ["outer", "outer2"]
        assert [s.name for s in inner.spans()] == ["inner"]

    def test_annotate_targets_nearest_kind(self):
        with trace_scope() as tr:
            with span("dispatch", "outer"):
                with span("rung", "tuned"):
                    assert annotate("dispatch", rung="tuned")
                    assert annotate(index=0)  # innermost open span
        disp, rung = list(tr.spans())
        assert disp.attrs["rung"] == "tuned"
        assert rung.attrs["index"] == 0

    def test_set_routes_typed_fields(self):
        with trace_scope() as tr:
            with span("dispatch", "d") as sp:
                sp.set(modeled_us=2.0, measured_us=4.0, blocks=(8, 128, 128))
        (sp,) = tr.spans()
        assert sp.modeled_us == 2.0
        assert sp.measured_us == 4.0
        assert sp.attrs == {"blocks": (8, 128, 128)}
        assert sp.drift_log == pytest.approx(math.log(2.0))

    def test_exception_still_closes_span(self):
        with trace_scope() as tr:
            with pytest.raises(RuntimeError):
                with span("tick", "t0"):
                    raise RuntimeError("boom")
            event("plan", "after")
        kinds = [s.kind for s in tr.spans()]
        assert kinds == ["tick", "plan"]  # plan is a sibling, not a child

    def test_open_span_join(self):
        from repro.obs import attribution

        with trace_scope() as tr:
            with attribution.dispatch("dense", m=1, k=2, n=3) as outer:
                with attribution.dispatch("dense", m=9, backend="x") as inner:
                    assert inner is outer  # joined, not nested
        assert tr.digest()["dispatch"] == 1
        (sp,) = [s for s in tr.spans() if s.kind == "dispatch"]
        assert sp.attrs["m"] == 1  # outer attrs win
        assert sp.attrs["backend"] == "x"  # inner fills gaps


# ------------------------------------------------------ metrics registry
class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = Registry()
        reg.counter("c").inc()
        reg.counter("c").inc(4)
        assert reg.value("c") == 5
        reg.gauge("g_last", mode="last").set(3)
        reg.gauge("g_last", mode="last").set(1)
        assert reg.value("g_last") == 1
        reg.gauge("g_max", mode="max").set(3)
        reg.gauge("g_max", mode="max").set(1)  # never rolls back
        assert reg.value("g_max") == 3
        h = reg.histogram("h")
        h.observe_many([1.0, 2.0, 3.0, 4.0])
        assert h.count() == 4
        assert h.percentile(50) == 2.0
        assert h.percentile(99) == 4.0

    def test_kind_conflicts_raise(self):
        reg = Registry()
        reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")
        reg.gauge("g", mode="max")
        with pytest.raises(ValueError):
            reg.gauge("g", mode="last")
        with pytest.raises(ValueError):
            reg.histogram("x")

    def test_counts_merges_and_sorts(self):
        reg = Registry()
        reg.counter("b").inc(2)
        reg.counter("zero")  # never incremented: elided
        reg.gauge("a", mode="max").set(7)
        reg.histogram("h").observe(1.0)  # histograms not in counts()
        assert reg.counts() == {"a": 7, "b": 2}

    def test_reset_clears_everything(self):
        reg = Registry()
        reg.counter("c").inc()
        reg.histogram("h").observe(1.0)
        reg.reset()
        assert reg.counts() == {}
        assert reg.histograms() == {}

    def test_percentile_nearest_rank(self):
        vals = [10.0, 20.0, 30.0, 40.0]
        assert percentile_nearest_rank(vals, 50) == 20.0
        assert percentile_nearest_rank(vals, 95) == 40.0
        assert percentile_nearest_rank([7.0], 1) == 7.0


# ----------------------------------------------------- health facade
class TestHealthFacade:
    def test_counters_route_through_registry(self):
        health.record("retries", 2)
        assert health.get("retries") == 2
        assert REGISTRY.value("retries") == 2
        assert health.snapshot() == {"retries": 2}

    def test_fallback_level_is_max_gauge(self):
        health.set_gauge("fallback_level", 2)
        health.set_gauge("fallback_level", 1)  # later lower rung: keep max
        assert health.get("fallback_level") == 2

    def test_provenance_fields_percentiles(self):
        health.record("serve_admitted", 3)
        REGISTRY.histogram("serve_ttft").observe_many([1.0, 2.0, 9.0])
        REGISTRY.histogram("drift/m1k2n3b1").observe(0.5)  # excluded
        fields = health.provenance_fields()
        assert fields["serve_admitted"] == 3
        assert fields["serve_ttft_p50"] == 2
        assert fields["serve_ttft_p99"] == 9
        assert not any(k.startswith("drift/") for k in fields)

    def test_percentile_default_vs_raise(self):
        with pytest.raises(ValueError):
            percentile([], 50)
        assert percentile([], 50, default=0) == 0.0

    def test_serve_telemetry_histograms(self):
        t = ServeTelemetry()
        t.observe_admission(0)
        t.observe_first_token(2)
        t.observe_completion(5, 3)
        t.record_health()
        assert REGISTRY.histogram("serve_ttft").count() == 1
        fields = health.provenance_fields()
        assert fields["serve_latency_p95"] == 5


# --------------------------------------------------------- attribution
class TestAttribution:
    def test_disarmed_dispatch_costs_nothing(self):
        a, b = _mats()
        ops.skew_matmul(a, b)
        assert health.snapshot() == {}
        assert not REGISTRY.histograms()

    def test_armed_dispatch_full_quad(self):
        a, b = _mats()
        with trace_scope(clock=SimClock()) as tr:
            ops.skew_matmul(a, b)
        (sp,) = [s for s in tr.spans() if s.kind == "dispatch"]
        assert sp.attrs["rung"] in ("tuned", "modeled")
        assert sp.modeled_us is not None
        assert sp.measured_us == sp.modeled_us  # sim clock
        assert sp.attrs["shape_class"] == "m8k256n512b1"
        assert health.get("obs_dispatches") == 1
        rep = drift_report()
        assert rep["max_abs_log"] == 0.0
        assert rep["accepted"]
        assert rep["classes"]["m8k256n512b1"]["count"] == 1

    def test_skewmm_xla_reference_rung(self):
        a, b = _mats()
        with trace_scope(clock=SimClock()) as tr:
            skewmm.matmul(a, b, backend="xla")
        (sp,) = [s for s in tr.spans() if s.kind == "dispatch"]
        assert sp.attrs["rung"] == "reference"
        assert sp.attrs["kernel"] == "xla_dot"
        assert sp.measured_us == sp.modeled_us

    def test_tuned_path_annotates_tune_key(self):
        from repro.tune import runtime as tune_runtime
        from repro.tune.cache import TuneCache

        a, b = _mats()
        with tune_runtime.use_cache(TuneCache()), mm_config(
            plan_mode="tuned"
        ):
            with trace_scope(clock=SimClock()) as tr:
                ops.skew_matmul(a, b)
        (sp,) = [s for s in tr.spans() if s.kind == "dispatch"]
        assert "tune_key" in sp.attrs
        assert sp.attrs["tune_hit"] is False  # empty cache: miss, degrade
        tune_events = [s for s in tr.spans() if s.kind == "tune"]
        assert tune_events and tune_events[0].name == sp.attrs["tune_key"]

    def test_rung_spans_on_laddered_path(self):
        a, b = _mats()
        with trace_scope() as tr:
            ops.skew_matmul(a, b)
        rungs = [s for s in tr.spans() if s.kind == "rung"]
        assert rungs
        assert rungs[-1].name in ("tuned", "modeled")

    def test_drift_report_threshold(self):
        REGISTRY.histogram("drift/m1k2n3b1").observe(MAX_LOG_SPREAD * 2)
        REGISTRY.histogram("drift/m4k2n3b1").observe(MAX_LOG_SPREAD / 2)
        rep = drift_report()
        assert not rep["accepted"]
        assert rep["classes_total"] == 2
        assert rep["classes_accepted"] == 1
        assert not rep["classes"]["m1k2n3b1"]["accepted"]
        assert rep["classes"]["m4k2n3b1"]["accepted"]


# ----------------------------------------------------------- exporters
class TestExport:
    def _trace(self):
        with trace_scope(clock=SimClock()) as tr:
            with span("tick", "t0", tick=0):
                event("plan", "dense/modeled", m=4, modeled_us=1.5)
        return tr

    def test_render_text_deterministic(self):
        tr = self._trace()
        assert render_text(tr) == render_text(tr)
        text = render_text(tr)
        assert "tick:t0" in text
        assert "  plan:dense/modeled" in text
        assert "modeled=1.500us" in text

    def test_chrome_roundtrip(self, tmp_path):
        tr = self._trace()
        doc = to_chrome(tr)
        validate_chrome(doc)
        assert len(doc["traceEvents"]) == tr.digest()["total"]
        path = tmp_path / "t.json"
        export_chrome(tr, str(path))
        reread = json.loads(path.read_text())
        assert reread == doc
        validate_chrome(reread)

    def test_chrome_synthetic_layout_nests(self):
        tr = self._trace()
        evs = {e["cat"]: e for e in to_chrome(tr)["traceEvents"]}
        tick, plan = evs["tick"], evs["plan"]
        assert tick["ts"] <= plan["ts"]
        assert plan["ts"] + plan["dur"] <= tick["ts"] + tick["dur"]

    def test_validate_chrome_rejects_bad(self):
        with pytest.raises(ValueError):
            validate_chrome({"no_events": []})
        bad = {"traceEvents": [{"name": "x", "cat": "y", "ph": "B",
                                "ts": 0, "dur": 1, "pid": 0, "tid": 0,
                                "args": {}}]}
        with pytest.raises(ValueError):
            validate_chrome(bad)


# ---------------------------------------------------------- provenance
class TestProvenance:
    def test_trace_digest_captured_when_armed(self):
        with trace_scope():
            event("plan", "p")
            prov = Provenance.capture()
        assert prov.trace_digest == {"plan": 1, "total": 1}
        rec = BenchResult(name="r", suite="s", axes={}, metrics={},
                  info={}, provenance=prov)
        back = BenchResult.from_json(json.loads(json.dumps(rec.to_json())))
        assert back.provenance.trace_digest == {"plan": 1, "total": 1}

    def test_clean_record_unchanged(self):
        prov = Provenance.capture()
        assert prov.trace_digest is None
        rec = BenchResult(name="r", suite="s", axes={}, metrics={},
                  info={}, provenance=prov)
        assert "trace_digest" not in rec.to_json()["provenance"]

    def test_empty_trace_elided(self):
        with trace_scope():
            prov = Provenance.capture()
        assert prov.trace_digest is None


# ---------------------------------------------------------- concurrency
class TestConcurrency:
    def test_registry_counts_exact_under_threads(self):
        reg = Registry()
        n_threads, n_inc = 8, 500

        def work():
            for _ in range(n_inc):
                reg.inc("c")
                reg.histogram("h").observe(1.0)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.value("c") == n_threads * n_inc
        assert reg.histogram("h").count() == n_threads * n_inc

    def test_health_facade_threadsafe(self):
        def work():
            for _ in range(300):
                health.record("retries")

        threads = [threading.Thread(target=work) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert health.get("retries") == 1800

    def test_span_tree_thread_isolation(self):
        """A scope armed on one thread never sees another thread's spans,
        and a thread with no scope stays disarmed (NULL_SPAN)."""
        errs = []
        barrier = threading.Barrier(2)

        def traced():
            try:
                with trace_scope() as tr:
                    barrier.wait(timeout=5)
                    for i in range(50):
                        event("plan", f"p{i}")
                    barrier.wait(timeout=5)
                    assert len(tr.roots) == 50
            except Exception as e:  # pragma: no cover - diagnostic
                errs.append(e)

        def untraced():
            try:
                barrier.wait(timeout=5)
                # _ARMED is nonzero (other thread), but this thread has
                # no layer: still disarmed here.
                assert not tracing()
                with span("tick") as sp:
                    assert sp is NULL_SPAN
                barrier.wait(timeout=5)
            except Exception as e:  # pragma: no cover - diagnostic
                errs.append(e)

        ts = [threading.Thread(target=traced),
              threading.Thread(target=untraced)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert errs == []
        assert not tracing()
        assert obs_spans._ARMED == 0

    def test_registry_reset_during_armed_trace(self):
        """guard.reset() mid-trace clears counters but leaves the span
        tree intact — the two stores are independent."""
        a, b = _mats()
        with trace_scope(clock=SimClock()) as tr:
            ops.skew_matmul(a, b)
            guard.reset()
            ops.skew_matmul(a, b)
        assert health.get("obs_dispatches") == 1  # post-reset dispatch only
        assert len([s for s in tr.spans() if s.kind == "dispatch"]) == 2


# -------------------------------------------------------- profiler sink
def _tiny_scheduler():
    import jax

    from repro.configs.base import get_config
    from repro.models.model import build_model
    from repro.serve.sched import BucketTable, Scheduler, scripted_trace

    cfg = get_config("phi4-mini-3.8b").reduced()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    table = BucketTable.for_workload(max_batch=2, max_prompt=8, max_new=3)
    sched = Scheduler(params, cfg, table)
    for r in scripted_trace([(0, 3, 3), (0, 5, 3)],
                            vocab_size=cfg.vocab_size, seed=1):
        sched.submit(r)
    return sched


def _sink_hooks():
    import gc

    from jax._src import monitoring

    return (obs_spans._on_gc in gc.callbacks,
            obs_spans._on_jax_duration
            in monitoring.get_event_duration_listeners())


class _NoThreadLocal:
    """Stands in for the span stack: any look at it fails the test."""

    def __getattr__(self, name):
        raise AssertionError(f"disarmed site read the span stack ({name})")


class TestProfilerSink:
    def test_disarmed_step_is_one_integer_check(self, monkeypatch):
        sched = _tiny_scheduler()
        sched.step()                     # admit + prefill + decode
        before = REGISTRY.snapshot()
        monkeypatch.setattr(obs_spans, "_TLS", _NoThreadLocal())
        sched.step()                     # decode, bookkeeping, completion
        sched.step()
        assert sched.results
        assert _sink_hooks() == (False, False)
        assert REGISTRY.snapshot() == before

    def test_sink_registers_hooks_only_while_armed(self):
        assert _sink_hooks() == (False, False)
        with trace_scope(profiler=True):
            assert _sink_hooks() == (True, True)
            with trace_scope(profiler=True):
                assert _sink_hooks() == (True, True)
            assert _sink_hooks() == (True, True)   # the outer sink holds
        assert _sink_hooks() == (False, False)
        assert obs_spans._ARMED == 0 and obs_spans._SINKS == 0

    def test_sink_builds_no_tree(self):
        a, b = _mats()
        with trace_scope(profiler=True) as tr:
            assert not tracing()      # call sites build no span attributes
            with span("tick", "t0", tick=0) as sp:
                assert sp is NULL_SPAN
                assert event("plan", "p") is NULL_SPAN
                assert not annotate(x=1)
                assert current_span() is None
            ops.skew_matmul(a, b)
        assert tr.roots == []
        assert health.get("obs_dispatches") == 0
        with pytest.raises(ValueError):
            with trace_scope(SimClock(), profiler=True):
                pass

    def test_fresh_jit_and_gc_feed_the_histograms(self):
        import gc

        import jax

        def fresh_fn(x):
            return x * 3 + 1

        with trace_scope(profiler=True):
            jax.jit(fresh_fn)(jnp.ones(4)).block_until_ready()
            gc.collect()
        hists = REGISTRY.histograms()
        assert hists["jax_trace_ms"].count() >= 1
        assert hists["jax_trace_ms"].total() > 0
        assert hists["jax_trace_ms/fresh_fn"].count() == 1
        assert hists["jax_compile_or_load_ms"].count() >= 1
        assert hists["gc_ms"].count() >= 1
        assert REGISTRY.value("gc_collections") == hists["gc_ms"].count()
        # disarmed again: a fresh jit adds nothing
        n = hists["jax_trace_ms"].count()
        jax.jit(lambda x: x - 2)(jnp.ones(4)).block_until_ready()
        assert REGISTRY.histograms()["jax_trace_ms"].count() == n

    @pytest.fixture(scope="class")
    def step_trace(self, tmp_path_factory):
        """Host events [(name, start_ns, end_ns)] of an admitting and a
        decoding `step()`, in a profiler trace with the sink armed,
        read through JAX's own `ProfileData`."""
        import glob
        import gc

        import jax
        from jax.profiler import ProfileData

        sched = _tiny_scheduler()
        log_dir = str(tmp_path_factory.mktemp("profile"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("window"), \
                    trace_scope(profiler=True):
                sched.step()
                sched.step()
                gc.collect()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        return sorted(
            ((e.name, e.start_ns, e.start_ns + e.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events),
            key=lambda ev: ev[1])

    def test_spans_nest_in_tick_in_window(self, step_trace):
        def inside(inner, outer):
            return outer[1] <= inner[1] and inner[2] <= outer[2]

        (window,) = [e for e in step_trace if e[0] == "window"]
        ticks = [e for e in step_trace if e[0] == "repro.tick"]
        assert len(ticks) == 2 and all(inside(t, window) for t in ticks)
        for kind in ("admit", "prefill", "decode", "sync", "scatter",
                     "bookkeep", "dispatch"):
            found = [e for e in step_trace if e[0] == f"repro.{kind}"]
            assert found, kind
            assert all(any(inside(e, t) for t in ticks) for e in found)
        # the decoding tick reads its tokens after its model call
        decode = [e for e in step_trace if e[0] == "repro.decode"
                  and inside(e, ticks[1])]
        assert decode and any(
            e[0] == "repro.sync" and inside(e, ticks[1])
            and e[1] >= decode[-1][2] for e in step_trace)
        assert any(e[0] == "repro.gc" and inside(e, window)
                   for e in step_trace)

    def test_span_names_carry_no_arguments(self, step_trace):
        names = {e[0] for e in step_trace if e[0].startswith("repro.")}
        assert {"repro.tick", "repro.decode", "repro.sync"} <= names
        assert not any("#" in n for n in names)
