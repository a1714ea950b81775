"""flops.py, through the default program module's dims, against counts
made by hand for phi4-mini-3.8b."""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from chip import flops, harness  # noqa: E402

DENSE = harness.program({})
PHI4 = DENSE.Dims(layers=32, d_model=3072, heads=24, kv_heads=8, head_dim=128,
                  d_ff=8192, vocab=200064)


def test_layer_and_head_weights():
    # q 3072x3072, k and v 3072x1024, o 3072x3072, gate/up/down 3072x8192
    assert PHI4.layer_weights == 100_663_296
    assert PHI4.head_weights == 614_596_608
    # 3.84 B parameters with the tied embedding counted once
    assert 32 * PHI4.layer_weights + PHI4.head_weights == 3_835_822_080


def test_decode_step_by_hand():
    # two rows writing positions 9 and 99: per row 2 x 3,835,822,080
    # matmul FLOPs, plus QK^T and PV over 10 and 100 keys in 32 layers of
    # 24 heads of 128 (4 x 32 x 24 x 128 = 393,216 per key)
    assert flops.decode_flops(PHI4, [9, 99]) == 15_343_288_320 + 393_216 * 110
    # bf16 weights once, plus 128 KiB of K/V per real position
    assert flops.decode_bytes(PHI4, [9, 99]) == 7_671_644_160 + 131_072 * 110


def test_prefill_counts_each_causal_key_once():
    one = flops.prefill_flops(PHI4, 1)
    assert one == 2 * 32 * PHI4.layer_weights + 393_216 + 2 * 614_596_608
    n = 4
    assert flops.prefill_flops(PHI4, n) == (
        n * 2 * 32 * PHI4.layer_weights + 393_216 * (1 + 2 + 3 + 4)
        + 2 * 614_596_608)


def test_train_step_by_hand():
    l8 = DENSE.Dims(layers=8, d_model=3072, heads=24, kv_heads=8,
                    head_dim=128, d_ff=8192, vocab=200064)
    # forward per token: 2 x (8 layers + head) + attention over a mean of
    # 1024.5 keys (4 x 8 x 24 x 128 = 98,304 per key); backward twice that
    fwd = 2 * (805_306_368 + 614_596_608) + 98_304 * 1024.5
    assert flops.train_flops_per_token(l8, 2048) == pytest.approx(3 * fwd)
    assert flops.train_flops_per_token(l8, 2048) == pytest.approx(
        8_821_555_200)


def test_roofline_bound_names_its_side():
    t, side = flops.least_seconds(197e12, 1.0, 197e12, 819e9)
    assert (t, side) == (1.0, "compute")
    t, side = flops.least_seconds(1.0, 819e9, 197e12, 819e9)
    assert (t, side) == (1.0, "memory")


def test_mfu_readers_on_a_synthetic_record():
    from chip import harness, peaks

    pk = peaks.for_kind("TPU v5 lite")
    # two pure decode ticks of one second each, doing 197 TFLOP between
    # them: 50% of one chip's peak; one admitting tick of 2 s
    steps = [{"t0": 0.0, "t1": 1.0, "admit": False, "positions": [9],
              "decode_flops": 98.5e12, "prefill_flops": 0.0},
             {"t0": 1.0, "t1": 2.0, "admit": False, "positions": [10],
              "decode_flops": 98.5e12, "prefill_flops": 0.0},
             {"t0": 2.0, "t1": 4.0, "admit": True, "positions": [],
              "decode_flops": 0.0, "prefill_flops": 39.4e12}]
    rec = {"steps": steps, "peaks": pk, "dims": PHI4}
    assert harness.reader("mfu.decode")(rec) == pytest.approx(50.0)
    assert harness.reader("mfu.prefill")(rec) == pytest.approx(10.0)
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")
