"""Operations and bytes a cell's work needs, counted by its
configuration's own dims object (`dims(c)` of the configuration's
program module, `configs/<program>.py`).

These functions are what the metric readers call; each hands the count
to the dims object, so that a configuration of another architecture
brings its counts in its own file and no reader changes.
"""

from __future__ import annotations


def prefill_flops(dims, prompt_len: int) -> float:
    """A causal prefill of `prompt_len` tokens that emits the logits of
    its last position only."""
    return dims.prefill_flops(prompt_len)


def decode_flops(dims, positions) -> float:
    """One decode step of rows whose new token sits at `positions`."""
    return dims.decode_flops(positions)


def decode_bytes(dims, positions) -> float:
    """Least bytes one decode step reads."""
    return dims.decode_bytes(positions)


def attention_flops(dims, positions) -> float:
    """Operations under the `attention` scope of one decode step."""
    return dims.attention_flops(positions)


def attention_bytes(dims, positions) -> float:
    """Least bytes the `attention` scope of one decode step reads."""
    return dims.attention_bytes(positions)


def train_flops_per_token(dims, seq_len: int) -> float:
    """Forward and backward operations per token of a sequence of
    `seq_len` (recomputation is not counted)."""
    return dims.train_flops_per_token(seq_len)


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bw: float) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc, tb = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tb else (tb, "memory")
