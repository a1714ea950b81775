"""What the benchmark finds by name: cells, configurations, traffic
mixes, per-layer metric readers and correctness limits.

Everything that belongs to one configuration, mix, metric or cell is a
file of its own under the benchmark's root:

  configs/<config>.json      the configuration as it is run
  traffic/<mix>.json         the mix's parameters (read by traffic.py)
  metrics/<metric>.py        `read(record) -> float | None`
  limits/<cell>.json         {number: limit} for the correctness check

so a later change adds a cell by adding files and entries, and edits none.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]


def annotate(name: str, on: bool, **kw):
    """A host span in the profiler's trace (a step's when `step_num` is
    given), or nothing when the run is not traced."""
    if not on:
        return contextlib.nullcontext()
    import jax

    if "step_num" in kw:
        return jax.profiler.StepTraceAnnotation(name, **kw)
    return jax.profiler.TraceAnnotation(name)


class Refused(Exception):
    """The run cannot be made here (no chip, too few chips, unknown
    device); it prints no result."""


def load_benchmark(path: pathlib.Path | None = None) -> dict:
    with open(path or REPO / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r}; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, root: pathlib.Path = HERE) -> dict:
    with open(root / "configs" / f"{name}.json") as f:
        return json.load(f)


def limits(cell_name: str, root: pathlib.Path = HERE) -> dict:
    with open(root / "limits" / f"{cell_name}.json") as f:
        return json.load(f)


def reader(metric: str, root: pathlib.Path = HERE):
    """The `read` function of metrics/<metric>.py (names may hold dots),
    under `root` or among the benchmark's own."""
    path = root / "metrics" / f"{metric}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{metric}.py"
    return _load(path).read


def end_to_end(bench: dict, cell_name: str) -> list[dict]:
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(bench: dict, cell_name: str) -> list[dict]:
    """Per-layer metrics whose reader this cell must run: those that list
    it, and those without a list that move an end-to-end metric the cell
    reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell_name)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def program_config(c: dict, name: str):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig

    if c.get("partial_rotary_factor", 1.0) != 1.0 or c.get("rope_scaling"):
        raise ValueError(f"{name}: the program runs plain RoPE only")
    if c["hidden_act"] != "silu":
        raise ValueError(f"{name}: only SwiGLU MLPs are described here")
    return ModelConfig(
        name=name, family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim",
                       c["hidden_size"] // c["num_attention_heads"]),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        mlp_type="swiglu", rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        dtype=c["torch_dtype"])


def dims(c: dict):
    from chip.flops import Dims

    return Dims(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"],
                head_dim=c.get("head_dim",
                               c["hidden_size"] // c["num_attention_heads"]),
                d_ff=c["intermediate_size"], vocab=c["vocab_size"])


def reference(c: dict, root: pathlib.Path = HERE):
    """The configuration's plain reference module, beside its file (or
    among the benchmark's own)."""
    path = root / "configs" / f"{c['reference']}.py"
    if not path.exists():
        path = HERE / "configs" / f"{c['reference']}.py"
    return _load(path)


def _load(path: pathlib.Path):
    """Import a file found by name, once per path."""
    name = "chip_file_" + "".join(ch if ch.isalnum() else "_"
                                  for ch in str(path))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]
