"""BENCHMARK.json keeps to the contract's names and limits, and every
name in it resolves to its files."""

import json
import pathlib
import re
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
sys.path.insert(0, str(HERE.parent))

from chip import harness  # noqa: E402

BENCHES = [REPO / "BENCHMARK.json", HERE / "tests" / "fixture" / "BENCHMARK.json"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def _load(path):
    return json.loads(path.read_text())


def _root(path):
    return HERE if path == BENCHES[0] else path.parent


@pytest.mark.parametrize("path", BENCHES, ids=["benchmark", "fixture"])
def test_names_and_units_use_allowed_characters(path):
    b = _load(path)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = []
    for c in b["configs"]:
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    for w in b["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for m in b["end_to_end"] + b["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        ns = [x["name"] for x in b[group]]
        assert len(ns) == len(set(ns))
    ms = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(ms) == len(set(ms))
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(b)) <= 64 * 1024
    assert all(PATH.match(p) and ".." not in p for p in b["paths"])
    assert len(b["command"]) <= 32


@pytest.mark.parametrize("path", BENCHES, ids=["benchmark", "fixture"])
def test_every_name_resolves_to_its_files(path):
    b = _load(path)
    root = _root(path)
    for w in b["workloads"]:
        c = harness.config(w["config"], root)
        assert harness.reference(c, root).Arch.from_config(c)
        assert harness.limits(w["name"], root)
        assert json.loads((root / "traffic" / f"{w['traffic']}.json").read_text())
        for m in (harness.end_to_end(b, w["name"])
                  + harness.per_layer(b, w["name"])):
            assert callable(harness.reader(m["name"], root))
    for c in b["configs"]:
        assert (REPO / c["file"]).is_file()
        assert c["file"].startswith(b["paths"][0] + "/")


def test_cells_report_what_the_contract_asks():
    b = _load(BENCHES[0])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in b["workloads"]:
        mine = [m["name"] for m in harness.end_to_end(b, w["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        assert harness.per_layer(b, w["name"])
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m.get("workloads", []):
            assert m["moves"] in [x["name"]
                                  for x in harness.end_to_end(b, w)]
    assert all(c["name"] in {w["config"] for w in b["workloads"]}
               for c in b["configs"])
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)


def test_run_seconds_fit_a_full_check_of_24_cells():
    rs = _load(BENCHES[0])["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_roofline_and_mfu_metrics_are_shares():
    b = _load(BENCHES[0])
    for m in b["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    moved = {m["moves"] for m in b["per_layer"] if m["name"].endswith("_roofline")}
    mfu_moves = {m["moves"] for m in b["per_layer"] if "mfu" in m["name"]}
    assert moved <= mfu_moves
