"""The one generator of the benchmark's traffic, read from a mix's data
file (`traffic/<mix>.json`).

Every seed serves the same work: the sequence of request sizes (and of
open-loop arrival gaps) is drawn from the mix's own `sizes_seed`, and
`--seed` only reorders it within consecutive blocks of `shuffle_block`
requests and draws the prompt tokens.  A run consumes a prefix of the
sequence, so runs on different seeds differ in order and content, never
in how much there is to do.

Serving mixes (`"kind": "serve"`):
  loop         "closed" (`clients` callers, each sending its next request
               when its last completes, after `think_s`) or "open"
               (Poisson arrivals at `rate_per_s`, due whether or not the
               server keeps up);
  prompt, output  {"median", "sigma", "min", "max"}: lognormal lengths,
               rounded and clipped;
  pool         how many (prompt, output) pairs the fixed sequence holds;
               a run that uses more cycles through it again;
  shuffle_block  the block within which a seed reorders it (a closed
               loop's clients, so that its first requests are the same
               set on every seed).
Training mixes (`"kind": "train"`): `seq_len`, `batch`, and `zipf_a`,
the exponent of the token ids' Zipf marginal (ids taken modulo the
vocabulary), so that losses resemble text; `reference_rows`, how many
rows the plain reference takes at a time so that it fits.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str, root: pathlib.Path = HERE) -> dict:
    with open(root / "traffic" / f"{name}.json") as f:
        return json.load(f)


def seed32(seed: int) -> int:
    """A non-negative 31-bit seed for APIs that take no more, derived
    from any whole number."""
    return int(np.random.SeedSequence(abs(int(seed)) + (seed < 0) * 2**70)
               .generate_state(1)[0]) & 0x7FFFFFFF


def _lognormal_lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


class ServeTraffic:
    """Requests of one serving mix for one seed, in sending order."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        if spec["kind"] != "serve":
            raise ValueError(f"not a serving mix: {spec['kind']!r}")
        self.spec = spec
        self.vocab = vocab
        fixed = np.random.default_rng(spec["sizes_seed"])
        n = spec["pool"]
        prompts = _lognormal_lengths(fixed, spec["prompt"], n)
        outputs = _lognormal_lengths(fixed, spec["output"], n)
        self._rng = np.random.default_rng(seed32(seed))
        order = self._block_order(n, spec["shuffle_block"])
        self.prompt_lens = prompts[order]
        self.output_lens = outputs[order]
        if spec["loop"] == "open":
            gaps = fixed.exponential(1.0 / spec["rate_per_s"], n)
            self.gaps_s = gaps[self._block_order(n, spec["shuffle_block"])]
        self._sent = 0

    def _block_order(self, n: int, block: int) -> np.ndarray:
        return np.concatenate([lo + self._rng.permutation(min(block, n - lo))
                               for lo in range(0, n, block)])

    @property
    def max_prompt(self) -> int:
        return int(self.spec["prompt"]["max"])

    @property
    def max_output(self) -> int:
        return int(self.spec["output"]["max"])

    def next(self) -> tuple[int, np.ndarray, int]:
        """(request id, prompt token ids, output length) of the next
        request to send."""
        i = self._sent
        k = i % len(self.prompt_lens)
        self._sent += 1
        prompt = self._rng.integers(0, self.vocab, int(self.prompt_lens[k]),
                                    dtype=np.int64).astype(np.int32)
        return i, prompt, int(self.output_lens[k])

    def gap_s(self, i: int) -> float:
        """Seconds between open-loop request i - 1 and request i."""
        return float(self.gaps_s[i % len(self.gaps_s)])


class TrainBatches:
    """Step-addressable token batches (the `DataLoader` source interface):
    the same (seed, step) gives the same rows, and all rows differ."""

    def __init__(self, spec: dict, seed: int, vocab: int):
        if spec["kind"] != "train":
            raise ValueError(f"not a training mix: {spec['kind']!r}")
        self.spec = spec
        self.vocab = vocab
        self.seed = seed32(seed)

    def batch(self, step: int, batch_size: int, seq_len: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, step))
        z = rng.zipf(self.spec["zipf_a"], size=(batch_size, seq_len))
        return (z % self.vocab).astype(np.int32)
