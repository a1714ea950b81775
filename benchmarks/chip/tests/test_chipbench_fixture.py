"""A cell built from files alone (tests/fixture: its own BENCHMARK.json,
configuration, mixes, limits and one metric reader of its own) is found
and run by the harness without a change to any of its files.  The runs
skip the harness's look for a chip and use the CPU at toy widths."""

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chip import harness  # noqa: E402
from chip import run as bench_run  # noqa: E402

FIXTURE = HERE / "tests" / "fixture"
SEED = 2**40 + 21          # wider than 32 bits, as the driver's are


def run_fixture(cell, trace=0, seconds=5.0, **kw):
    bench = harness.load_benchmark(FIXTURE / "BENCHMARK.json")
    return bench_run.run_cell(bench, harness.cell(bench, cell), SEED,
                              seconds, trace, root=FIXTURE,
                              require_tpu=False, **kw)


def test_cpu_is_refused_where_a_chip_is_required():
    bench = harness.load_benchmark(FIXTURE / "BENCHMARK.json")
    with pytest.raises(harness.Refused):
        bench_run.run_cell(bench, harness.cell(bench, "tiny-chat"), 1, 1.0,
                           0, root=FIXTURE)


@pytest.mark.parametrize("trace", [0, 1])
def test_fixture_chat_cell_runs_from_its_files(trace):
    line = run_fixture("tiny-chat", trace)
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    if trace:
        # the fixture's own reader, found by name
        assert line["metrics"]["tokens_per_tick"]["value"] > 0
        assert "compiles_per_tick.chat" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"setup_s", "itl_p90_ms",
                                        "out_tokens_per_s"}
    assert line["device"]["count"] == 1
    json.dumps(line)


def test_fixture_open_loop_cell_reports_ttft():
    line = run_fixture("tiny-open")
    assert line["correct"] is True
    assert line["metrics"]["ttft_p90_ms"]["value"] > 0
    assert line["attempted"] > 4


def test_fixture_training_cell_runs_from_its_files():
    line = run_fixture("tiny-train")
    assert line["correct"] is True
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(line["checks"]) == {"loss_rel_gap", "grad_norm_gap",
                                   "change_norm_gap"}
