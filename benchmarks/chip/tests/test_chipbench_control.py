"""The correctness check fails what it must: the control (the plain
reference in fp8, the precision below the configuration's bfloat16, put
in the program's place) and faults planted in the timed path.  Toy widths
on the CPU; the harness's look for a chip is skipped."""

import pathlib
import sys

import jax.numpy as jnp
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from test_chipbench_fixture import run_fixture  # noqa: E402

from chip import harness  # noqa: E402

FIXTURE = HERE / "tests" / "fixture"


def _fails(line, name):
    c = line["checks"][name]
    return line["correct"] is False and not c["value"] <= c["limit"]


def test_serving_control_fails_the_limit():
    rec = {}
    run_fixture("tiny-chat", control=True, record=rec)
    lim = harness.limits("tiny-chat", FIXTURE)["widest_gap_logits"]
    assert rec["control"]["program_widest_gap_logits"] <= lim
    assert rec["control"]["control_widest_gap_logits"] > lim


def test_training_control_and_faults_fail_a_limit():
    rec = {}
    run_fixture("tiny-train", control=True, record=rec)
    lim = harness.limits("tiny-train", FIXTURE)
    ctl = rec["control"]
    assert all(ctl["program"][k] <= lim[k] for k in lim)
    for what in ("control_fp8", "fault_half_batch", "fault_state_unchanged"):
        assert any(ctl[what][k] > lim[k] for k in lim), what


# ---- faults planted in the program's timed path, seen as correct: false
def _decode_fault(kind):
    from repro.serve import engine

    real = engine.decode_step

    def faulty(params, cfg, cache, tokens, pos, mm=None):
        logits, new_cache = real(params, cfg, cache, tokens, pos, mm)
        if kind == "state_unchanged":
            return logits, cache
        if kind == "token_altered":
            return logits.at[:, 7].add(1e3), new_cache
        if kind == "half_batch":
            half = logits.shape[0] // 2
            return jnp.concatenate([logits[:half]] * 2), new_cache
        raise ValueError(kind)

    return faulty


@pytest.mark.parametrize("kind", ["state_unchanged", "token_altered",
                                  "half_batch"])
def test_serving_faults_come_out_incorrect(kind, monkeypatch):
    from repro.serve import engine

    monkeypatch.setattr(engine, "decode_step", _decode_fault(kind))
    line = run_fixture("tiny-chat")
    assert _fails(line, "widest_gap_logits")


def test_training_state_left_unchanged_comes_out_incorrect(monkeypatch):
    from repro.train.trainer import Trainer

    def step(self, batch):
        _, metrics = self.step_fn(self.state, batch)
        return metrics

    monkeypatch.setattr(Trainer, "step", step)
    line = run_fixture("tiny-train")
    assert _fails(line, "change_norm_gap")


def test_training_half_batch_comes_out_incorrect(monkeypatch):
    from repro.train import trainer as trainer_mod

    real = trainer_mod.make_train_step

    def halved(bundle, opt, ts_cfg):
        step = real(bundle, opt, ts_cfg)

        def train_step(state, batch):
            rows = batch["tokens"].shape[0] // 2
            return step(state, {"tokens": batch["tokens"][:rows]})

        return train_step

    monkeypatch.setattr(trainer_mod, "make_train_step", halved)
    line = run_fixture("tiny-train")
    assert line["correct"] is False


def test_training_exchange_left_out_comes_out_incorrect(monkeypatch):
    """Without the all-reduce a row-parallel matmul (the attention output
    and the MLP down projection) returns one shard's partial sum: a
    quarter of its contraction."""
    from repro.core import skewmm

    real = skewmm.matmul

    def partial(a, b, *args, **kw):
        k = a.shape[-1]
        if b.ndim == 2 and k > b.shape[-1]:          # down and output
            a = a.at[..., k // 4:].set(0)
        return real(a, b, *args, **kw)

    monkeypatch.setattr(skewmm, "matmul", partial)
    line = run_fixture("tiny-train")
    assert line["correct"] is False
