"""The seeded generator replays identically, serves the same work on
every seed, and matches the distributions its mixes state."""

import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))

from chip import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))
SERVE = [m for m in MIXES if traffic.load(m)["kind"] == "serve"]


def _draw(spec, seed, n=40):
    gen = traffic.ServeTraffic(spec, seed, vocab=1000)
    return [gen.next() for _ in range(n)], gen


@pytest.mark.parametrize("mix", SERVE)
def test_same_seed_replays(mix):
    spec = traffic.load(mix)
    a, _ = _draw(spec, 2**40 + 3)
    b, _ = _draw(spec, 2**40 + 3)
    for (ra, pa, oa), (rb, pb, ob) in zip(a, b):
        assert (ra, oa) == (rb, ob)
        np.testing.assert_array_equal(pa, pb)


@pytest.mark.parametrize("mix", SERVE)
def test_every_seed_serves_the_same_multiset(mix):
    spec = traffic.load(mix)
    _, g1 = _draw(spec, 1)
    _, g2 = _draw(spec, 987654321987)
    blk = spec["shuffle_block"]
    # every prefix of whole blocks holds the same sizes on every seed
    for end in range(blk, spec["pool"] + 1, blk):
        assert sorted(g1.prompt_lens[:end]) == sorted(g2.prompt_lens[:end])
        assert sorted(g1.output_lens[:end]) == sorted(g2.output_lens[:end])
        if spec["loop"] == "open":
            assert sorted(g1.gaps_s[:end]) == sorted(g2.gaps_s[:end])
    assert list(g1.prompt_lens) != list(g2.prompt_lens)   # another order


@pytest.mark.parametrize("mix", SERVE)
def test_lengths_match_the_stated_lognormal(mix):
    spec = traffic.load(mix)
    gen = traffic.ServeTraffic(spec, 5, vocab=1000)
    for lens, d in ((gen.prompt_lens, spec["prompt"]),
                    (gen.output_lens, spec["output"])):
        assert lens.min() >= d["min"] and lens.max() <= d["max"]
        # the median survives the clipping; a lognormal's log-spread is sigma
        assert np.median(lens) == pytest.approx(d["median"], rel=0.12)
        inner = lens[(lens > d["min"]) & (lens < d["max"])]
        assert np.std(np.log(inner)) == pytest.approx(d["sigma"], rel=0.3)
    assert spec["prompt"]["max"] + spec["output"]["max"] <= spec["max_len"]


def test_open_loop_gaps_are_poisson_at_the_stated_rate():
    spec = dict(traffic.load("docs-open"), pool=4000)
    gen = traffic.ServeTraffic(spec, 11, vocab=1000)
    gaps = gen.gaps_s
    assert np.mean(gaps) == pytest.approx(1 / spec["rate_per_s"], rel=0.05)
    # exponential: the spread equals the mean
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.1)


def test_training_batches_replay_and_all_rows_differ():
    spec = traffic.load("train-seq2048")
    a = traffic.TrainBatches(spec, 2**35 + 1, vocab=200064)
    b = traffic.TrainBatches(spec, 2**35 + 1, vocab=200064)
    x = a.batch(3, 4, 256)
    np.testing.assert_array_equal(x, b.batch(3, 4, 256))
    assert x.dtype == np.int32 and x.max() < 200064 and x.min() >= 0
    assert len({r.tobytes() for r in x}) == 4
    assert not np.array_equal(x, a.batch(4, 4, 256))


def test_seed32_takes_any_whole_number():
    seeds = [0, 1, 2**31, 2**33 + 5, 2**62, -7]
    out = [traffic.seed32(s) for s in seeds]
    assert all(0 <= s < 2**31 for s in out)
    assert len(set(out)) == len(out)
    assert traffic.seed32(2**33 + 5) == traffic.seed32(2**33 + 5)


def test_mix_files_are_plain_data():
    for m in MIXES:
        json.loads((HERE / "traffic" / f"{m}.json").read_text())
