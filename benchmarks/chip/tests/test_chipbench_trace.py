"""The trace reduction on a small synthetic trace."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

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
