"""Operations and bytes a dense GQA decoder's work needs, from its shapes.

The counts are of the mathematics, the same whatever implements it:
padding, recomputation and the layout a kernel picks are not needed work
and are not counted.  A multiply-add is two operations.  Embedding
lookups, norms, rotary and softmax are left out (a fraction of a percent
of the matmuls at these widths).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated_mlp: bool = True
    weight_bytes: int = 2        # bf16
    kv_bytes: int = 2            # bf16 cache

    @property
    def layer_weights(self) -> int:
        """Matmul weights of one layer (attention + MLP)."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        mlp = (3 if self.gated_mlp else 2) * self.d_model * self.d_ff
        return self.d_model * (q + 2 * kv) + q * self.d_model + mlp

    @property
    def head_weights(self) -> int:
        return self.d_model * self.vocab


def attn_flops(dims: Dims, keys: int) -> float:
    """One query attending to `keys` positions, all layers: QK^T and PV."""
    return 4.0 * dims.layers * dims.heads * dims.head_dim * keys


def dense_flops_per_token(dims: Dims) -> float:
    """Forward matmul operations of one token through every layer."""
    return 2.0 * dims.layers * dims.layer_weights


def head_flops(dims: Dims) -> float:
    return 2.0 * dims.head_weights


def prefill_flops(dims: Dims, prompt_len: int) -> float:
    """A causal prefill of `prompt_len` tokens that emits the logits of
    its last position only (the first generated token)."""
    n = prompt_len
    causal_keys = n * (n + 1) / 2          # sum over positions of keys seen
    return (n * dense_flops_per_token(dims)
            + 4.0 * dims.layers * dims.heads * dims.head_dim * causal_keys
            + head_flops(dims))


def decode_flops(dims: Dims, positions) -> float:
    """One decode step of rows whose new token sits at `positions` (each
    attends to position + 1 keys), logits for every row."""
    rows = len(positions)
    return (rows * (dense_flops_per_token(dims) + head_flops(dims))
            + sum(attn_flops(dims, p + 1) for p in positions))


def decode_bytes(dims: Dims, positions) -> float:
    """Least bytes one decode step reads: every weight once, and the K/V
    of each live row's real positions."""
    weights = (dims.layers * dims.layer_weights + dims.head_weights)
    kv_per_pos = 2 * dims.layers * dims.kv_heads * dims.head_dim
    return (weights * dims.weight_bytes
            + sum((p + 1) * kv_per_pos * dims.kv_bytes for p in positions))


def train_flops_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward operations per token of a causal sequence of
    `seq_len` (3x the forward; recomputation is not counted), with the
    loss's logits over the whole vocabulary at every position."""
    mean_keys = (seq_len + 1) / 2
    fwd = (dense_flops_per_token(dims) + head_flops(dims)
           + attn_flops(dims, mean_keys))
    return 3.0 * fwd


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bw: float) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc, tb = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tb else (tb, "memory")
