"""The trace reduction on small synthetic traces, and on one recorded
on the CPU with the program's spans and JAX's stages."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3] / "src"))

from chip import trace_reduce as tr  # noqa: E402

MS = 1e6  # nanoseconds


def _ops():
    # two overlapping ops, one alone, one collective, in [0, 100] ms
    return [("%fusion.1 = bf16[8] fusion(x)", 10 * MS, 30 * MS),
            ("%convolution.2 = bf16[8] convolution(a, b)", 20 * MS, 40 * MS),
            ("%all-reduce.3 = f32[8] all-reduce(y)", 60 * MS, 70 * MS),
            ("%fusion.1 = bf16[8] fusion(x)", 80 * MS, 85 * MS)]


def test_busy_union_and_idle_share():
    merged = tr.merge(_ops())
    assert merged == [(10 * MS, 40 * MS), (60 * MS, 70 * MS),
                      (80 * MS, 85 * MS)]
    assert tr.busy_within(merged, 0, 100 * MS) == pytest.approx(45 * MS)
    # idle share of the window: 1 - busy / window
    assert 1 - tr.busy_within(merged, 0, 100 * MS) / (100 * MS) == \
        pytest.approx(0.55)
    # a window that cuts an interval counts only its inside
    assert tr.busy_within(merged, 35 * MS, 65 * MS) == pytest.approx(10 * MS)


def test_per_span_device_time():
    merged = tr.merge(_ops())
    spans = [("bench.tick.decode", 0, 50 * MS),
             ("bench.tick.admit", 50 * MS, 100 * MS)]
    assert tr.span_busy(merged, spans) == pytest.approx([30 * MS, 15 * MS])


def test_collective_time_and_top_ops():
    ops = _ops()
    assert tr.collective_ns(ops, 0, 100 * MS) == pytest.approx(10 * MS)
    assert tr.collective_ns(ops, 65 * MS, 100 * MS) == pytest.approx(5 * MS)
    assert tr.is_collective("%all-gather-start.7 = (f32[2]) all-gather-start(x)")
    assert not tr.is_collective("%fusion.9 = f32[2] fusion(%all-reduce.1)")
    top = tr.top_ops(ops, 0, 100 * MS, k=2)
    assert top == [["fusion.1", pytest.approx(0.025)],
                   ["convolution.2", pytest.approx(0.02)]]


def test_idle_gaps_are_labelled_by_host_spans():
    merged = tr.merge(_ops())
    host = [("bench.tick.decode", 0, 50 * MS),
            ("bench.generator", 42 * MS, 58 * MS),
            ("bench.tick.admit", 50 * MS, 100 * MS)]
    gaps = tr.idle_gaps(merged, 0, 100 * MS, host, k=3)
    assert [g[1] for g in gaps] == pytest.approx([0.02, 0.015, 0.01])
    # [40, 60] is covered 16 ms by the generator, 10 by each tick
    assert gaps[0][0] == "bench.generator"
    assert gaps[1][0] == "bench.tick.admit"     # [85, 100]
    assert gaps[2][0] == "bench.tick.decode"    # [0, 10]


def test_idle_gaps_take_the_innermost_span_of_either_list():
    merged = [(0, 10 * MS), (90 * MS, 100 * MS)]
    bench = [("bench.tick.decode", 5 * MS, 95 * MS)]
    program = [("repro.tick", 6 * MS, 94 * MS), ("repro.gc", 20 * MS, 80 * MS),
               ("backend_compile_and_load", 82 * MS, 84 * MS)]
    spans = sorted(bench + program, key=lambda s: (s[1], -s[2]))
    # [10, 90]: the tick and repro.tick cover all 80 ms, the GC 60 of
    # them: the innermost span over more than half of the gap is the GC
    assert tr.idle_gaps(merged, 0, 100 * MS, spans, k=1) == [
        ["repro.gc", pytest.approx(0.08)]]
    # with no GC, the program's tick, inside the benchmark's
    assert tr.idle_gaps(merged, 0, 100 * MS, spans[:2], k=1)[0][0] == \
        "repro.tick"
    # the benchmark's own list alone labels it as before
    assert tr.idle_gaps(merged, 0, 100 * MS, bench, k=1)[0][0] == \
        "bench.tick.decode"
    # no span over half of it: the one that covers most
    assert tr.idle_gaps(merged, 0, 100 * MS, program[2:] + [
        ("repro.sync", 60 * MS, 89 * MS)], k=1)[0][0] == "repro.sync"


def test_innermost_counts_an_op_nested_in_a_loop_once():
    ops = [("while", 0, 100), ("a", 10, 30), ("b", 30, 50), ("c", 120, 130),
           ("d", 125, 140)]
    pieces = tr.innermost(ops)
    assert pieces == [("while", 0, 10), ("a", 10, 30), ("b", 30, 50),
                      ("while", 50, 100), ("c", 120, 125), ("d", 125, 140)]
    # the pieces cover the busy union exactly
    assert sum(e - s for _, s, e in pieces) == tr.busy_within(
        tr.merge(ops), 0, 200)


# two compiled decode programs: the same instruction names, other shapes
# and scopes; a third module (an eager argmax) is in neither
_HLO = {
    4: """HloModule jit_step, is_scheduled=true
ENTRY %main {
  %while.19 = (s32[], bf16[4,8]{1,0:T(4,128)}) while((s32[], bf16[4,8]) %t), condition=%c, body=%b, metadata={op_name="jit(step)/while"}
  %fusion.1 = bf16[4,8]{1,0:T(4,128)} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/while/body/closed_call/attention/while/body/exp"}
  %fusion.2 = bf16[4,8]{1,0:T(4,128)} fusion(%p), kind=kOutput, calls=%f2, metadata={op_name="jit(step)/while/body/closed_call/mlp/mm.m1k8n8b4/dot_general"}
  ROOT %fusion.3 = f32[4,16]{1,0} fusion(%p), kind=kOutput, calls=%f3, metadata={op_name="jit(step)/lm_head/mm.m4k8n16b1/dot_general"}
}""",
    8: """HloModule jit_step, is_scheduled=true
ENTRY %main {
  %while.19 = (s32[], bf16[8,8]{1,0:T(8,128)}) while((s32[], bf16[8,8]) %t), condition=%c, body=%b, metadata={op_name="jit(step)/while"}
  %fusion.1 = bf16[8,8]{1,0:T(8,128)} fusion(%p), kind=kOutput, calls=%f1, metadata={op_name="jit(step)/while/body/closed_call/mm.m1k8n8b8/dot_general"}
  %fusion.2 = bf16[8,8]{1,0:T(8,128)} fusion(%p), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/while/body/closed_call/attention/kv_write/scatter"}
  ROOT %fusion.3 = f32[8,16]{1,0} fusion(%p), kind=kOutput, calls=%f3, metadata={op_name="jit(step)/rsqrt"}
}""",
}


def _op(name, shape, opcode, s, e):
    # as the trace prints an op: its operands carry their shapes
    return (f"%{name} = {shape} {opcode}(bf16[4,8]{{1,0}} %p), kind=kLoop",
            s * MS, e * MS)


def test_scope_split_joins_each_run_with_its_program():
    progs = [tr.hlo_ops(_HLO[4]), tr.hlo_ops(_HLO[8])]
    assert progs[0]["fusion.1"] == (
        "bf16[4,8]{1,0:T(4,128)} fusion",
        "jit(step)/while/body/closed_call/attention/while/body/exp")
    w4 = "(s32[], bf16[4,8]{1,0:T(4,128)})"
    w8 = "(s32[], bf16[8,8]{1,0:T(8,128)})"
    b4, b8 = "bf16[4,8]{1,0:T(4,128)}", "bf16[8,8]{1,0:T(8,128)}"
    ops = [  # a run of the batch-4 program in [0, 40]
        _op("while.19", w4, "while", 0, 30),
        _op("fusion.1", b4, "fusion", 2, 12),      # attention
        _op("fusion.2", b4, "fusion", 12, 20),     # mm, inside mlp
        _op("fusion.3", "f32[4,16]{1,0}", "fusion", 30, 36),   # lm_head
        # the eager argmax after it, in no decode program
        _op("reduce.7", "s32[4]{0}", "reduce", 40, 44),
        # a run of the batch-8 program in [50, 80]
        _op("while.19", w8, "while", 50, 70),
        _op("fusion.1", b8, "fusion", 52, 60),     # mm
        _op("fusion.2", b8, "fusion", 60, 64),     # attention (kv_write)
        _op("fusion.3", "f32[8,16]{1,0}", "fusion", 70, 78),   # unscoped
    ]
    modules = [("jit_step(111)", 0, 38 * MS), ("jit__argmax(5)", 40 * MS, 45 * MS),
               ("jit_step(222)", 50 * MS, 79 * MS)]
    spans = [("bench.tick.decode", 0, 46 * MS),
             ("bench.tick.decode", 48 * MS, 75 * MS)]
    got = tr.scope_split(ops, modules, progs, spans)
    assert got == pytest.approx({
        "attention": 10 * MS + 4 * MS, "mm": 8 * MS + 8 * MS,
        "lm_head": 6 * MS,
        # the loops outside their ops (12 + 8), argmax 4, rsqrt 5 of 8
        "unscoped": 12 * MS + 8 * MS + 4 * MS + 5 * MS})
    busy = sum(tr.span_busy(tr.merge(ops), spans))
    assert sum(got.values()) == pytest.approx(busy)
    # without the programs every instant is unscoped, and none is lost
    bare = tr.scope_split(ops, modules, [], spans)
    assert bare["unscoped"] == pytest.approx(busy)


def test_load_keeps_program_spans_and_jax_stages(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import obs

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with obs.trace_scope(profiler=True):
            with obs.span("tick"):
                y = jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0))
                np.asarray(y)
    jax.profiler.stop_trace()
    trace = tr.load(tr.find_xplane(str(tmp_path)))
    names = {n for n, _, _ in trace.program}
    assert {"repro.tick", "lower_sharding_computation",
            "backend_compile_and_load"} <= names
    assert all(n.startswith("repro.") or n in tr.JAX_STAGES for n in names)
    assert [n for n, _, _ in trace.host] == ["bench.window"]
    tick = next(s for s in trace.program if s[0] == "repro.tick")
    window = trace.host[0]
    assert window[1] <= tick[1] <= tick[2] <= window[2]


def test_compile_stages_are_counted_in_milliseconds_per_tick():
    import jax.monitoring as mon

    from chip import harness
    from chip.run import STAGES, CompileCounter

    counter = CompileCounter().reset()
    for name, secs in ((STAGES[0], 0.002), (STAGES[1], 0.030),
                       (STAGES[2], 0.008), ("/jax/other_duration", 5.0)):
        mon.record_event_duration_secs(name, secs)
    counts = counter.read()
    assert counts["stage_ms"] == pytest.approx(40.0)
    assert counts["loaded_or_compiled"] == 1
    rec = {"compile_counts": counts, "steps": [{}] * 4}
    assert harness.reader("compile_ms_per_tick.chat")(rec) == \
        pytest.approx(10.0)
    assert harness.reader("compile_ms_per_tick.docs")(
        dict(rec, steps=[])) is None
