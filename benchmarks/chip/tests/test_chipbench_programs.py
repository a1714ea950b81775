"""A configuration brings its program config and its work counts in its
own files: phi4's files, which name no program, run the dense GQA
default and count exactly what the dense formulas below count; a
fixture of another architecture (multi-query attention, a non-gated GELU
MLP) runs from its own program module, reference, counts and reader,
and its checks fail the control and a planted fault."""

import pathlib
import random
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from test_chipbench_control import _decode_fault, _fails  # noqa: E402
from test_chipbench_fixture import FIXTURE, run_fixture  # noqa: E402

from chip import flops, harness, peaks  # noqa: E402

PHI4_FILES = ["phi4-mini-3.8b", "phi4-mini-3.8b-l8-train"]


# ---- the dense GQA counts, written out on their own
def _layer_weights(c):
    d, ff = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return d * (q + 2 * kv) + q * d + 3 * d * ff


def _per_key(c):
    return 4.0 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"]


def _dense(c):
    return 2.0 * c["num_hidden_layers"] * _layer_weights(c)


def _head(c):
    return 2.0 * (c["hidden_size"] * c["vocab_size"])


def _prefill(c, n):
    return n * _dense(c) + _per_key(c) * (n * (n + 1) / 2) + _head(c)


def _decode(c, positions):
    return (len(positions) * (_dense(c) + _head(c))
            + sum(_per_key(c) * (p + 1) for p in positions))


def _bytes(c, positions):
    weights = (c["num_hidden_layers"] * _layer_weights(c)
               + c["hidden_size"] * c["vocab_size"])
    per_pos = 2 * c["num_hidden_layers"] * c["num_key_value_heads"] \
        * c["head_dim"] * 2
    return weights * 2 + sum((p + 1) * per_pos for p in positions)


def _train(c, seq):
    return 3.0 * (_dense(c) + _head(c) + _per_key(c) * ((seq + 1) / 2))


def _positions(n=200):
    rng = random.Random(15)
    return [[rng.randrange(0, 2112) for _ in range(rng.randrange(1, 17))]
            for _ in range(n)]


@pytest.mark.parametrize("name", PHI4_FILES)
def test_phi4_runs_the_dense_default_config(name):
    from repro.configs.base import ModelConfig

    c = harness.config(name)
    assert "program" not in c
    assert harness.program(c).model_config(c, name) == ModelConfig(
        name=name, family="dense", n_layers=c["num_hidden_layers"],
        d_model=3072, n_heads=24, n_kv_heads=8, head_dim=128, d_ff=8192,
        vocab_size=200064, mlp_type="swiglu", rope_theta=10000.0,
        tie_embeddings=True, norm_eps=1e-05, dtype="bfloat16")


@pytest.mark.parametrize("name", PHI4_FILES)
def test_phi4_counts_are_the_dense_counts_to_the_bit(name):
    c = harness.config(name)
    c.setdefault("head_dim", c["hidden_size"] // c["num_attention_heads"])
    d = harness.program(c).dims(c)
    for n in range(1, 2100, 13):
        assert flops.prefill_flops(d, n) == _prefill(c, n)
    for pos in _positions():
        assert flops.decode_flops(d, pos) == _decode(c, pos)
        assert flops.decode_bytes(d, pos) == _bytes(c, pos)
        assert flops.attention_bytes(d, pos) + 2 * (
            c["num_hidden_layers"] * _layer_weights(c)
            + c["hidden_size"] * c["vocab_size"]) == _bytes(c, pos)
    for seq in (77, 1024, 2048):
        assert flops.train_flops_per_token(d, seq) == _train(c, seq)


def test_phi4_readers_read_the_dense_counts():
    """decode_roofline, mfu.decode and mfu.prefill on a record whose
    counts come from the dims object equal the same readers on the
    dense formulas."""
    c = harness.config("phi4-mini-3.8b")
    d = harness.program(c).dims(c)
    pos = _positions(12)
    steps = [{"t0": i, "t1": i + 0.06, "admit": i % 4 == 0, "positions": p,
              "prefill_flops": flops.prefill_flops(d, 300 + i),
              "decode_flops": flops.decode_flops(d, p)}
             for i, p in enumerate(pos)]
    frozen = [dict(s, prefill_flops=_prefill(c, 300 + i),
                   decode_flops=_decode(c, s["positions"]))
              for i, s in enumerate(steps)]
    assert steps == frozen
    trace = {"step_spans_s": [0.06] * 12, "step_busy_s": [0.055] * 12}
    pk = peaks.for_kind("TPU v5 lite")
    rec = {"steps": steps, "dims": d, "peaks": pk, "trace": trace}

    class FrozenDims:
        def decode_flops(self, p):
            return _decode(c, p)

        def decode_bytes(self, p):
            return _bytes(c, p)

    for metric in ("decode_roofline", "mfu.decode", "mfu.prefill"):
        read = harness.reader(metric)
        assert read(rec) == read(dict(rec, steps=frozen, dims=FrozenDims()))
        assert read(rec) > 0


# ---- a second architecture from its files alone
def test_mqa_gelu_fixture_is_built_and_counted_by_its_own_module():
    c = harness.config("tiny-mqa-gelu", FIXTURE)
    prog = harness.program(c, FIXTURE)
    cfg = prog.model_config(c, "tiny-mqa-gelu")
    assert (cfg.mlp_type, cfg.n_kv_heads) == ("gelu", 1)
    d = prog.dims(c)
    # two MLP matrices: 64 x (64 + 2 x 16) + 64 x 64 + 2 x 64 x 256
    assert d.layer_weights == 6144 + 4096 + 32768
    assert flops.decode_flops(d, [3]) == (
        2.0 * 2 * d.layer_weights + 2.0 * 64 * 512 + 4.0 * 2 * 4 * 16 * 4)
    assert d.mlp_flops_per_token() == 2.0 * 2 * 32768
    with pytest.raises(ValueError):
        harness.program({}).model_config(c, "tiny-mqa-gelu")


@pytest.mark.parametrize("trace", [0, 1])
def test_mqa_gelu_cell_runs_from_its_files(trace):
    line = run_fixture("tiny-mqa-chat", trace)
    assert line["correct"] is True
    if trace:
        share = line["metrics"]["mlp_share_of_decode_flops"]["value"]
        assert 0 < share < 100
    else:
        assert set(line["metrics"]) == {"setup_s", "itl_p90_ms",
                                        "out_tokens_per_s"}


def test_mqa_gelu_control_fails_the_limit():
    rec = {}
    run_fixture("tiny-mqa-chat", control=True, record=rec)
    lim = harness.limits("tiny-mqa-chat", FIXTURE)["widest_gap_logits"]
    assert rec["control"]["program_widest_gap_logits"] <= lim
    assert rec["control"]["control_widest_gap_logits"] > lim


def test_mqa_gelu_decode_fault_comes_out_incorrect(monkeypatch):
    from repro.serve import engine

    monkeypatch.setattr(engine, "decode_step", _decode_fault("token_altered"))
    assert _fails(run_fixture("tiny-mqa-chat"), "widest_gap_logits")
