"""The program config and the work counts of a dense GQA decoder with
SwiGLU MLPs and plain RoPE: the default `"program"` of a configuration
file (phi4-mini-3.8b and its 8-layer training stage).

A program module gives
  model_config(c, name)  the program's `ModelConfig` for the file `c`;
  dims(c)                an object whose methods count the work:
                         prefill_flops(prompt_len), decode_flops(positions),
                         decode_bytes(positions),
                         train_flops_per_token(seq_len), and per named
                         scope a metric reads, its least work:
                         attention_flops(positions),
                         attention_bytes(positions).

The counts are of the mathematics, the same whatever implements it:
padding, recomputation and the layout a kernel picks are not needed work
and are not counted.  A multiply-add is two operations.  Embedding
lookups, norms, rotary and softmax are left out (a fraction of a percent
of the matmuls at these widths).
"""

from __future__ import annotations

import dataclasses


def model_config(c: dict, name: str):
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


def dims(c: dict) -> "Dims":
    return Dims(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"],
                head_dim=c.get("head_dim",
                               c["hidden_size"] // c["num_attention_heads"]),
                d_ff=c["intermediate_size"], vocab=c["vocab_size"])


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    weight_bytes: int = 2        # bf16
    kv_bytes: int = 2            # bf16 cache

    @property
    def layer_weights(self) -> int:
        """Matmul weights of one layer (attention + gate, up and down)."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        mlp = 3 * self.d_model * self.d_ff
        return self.d_model * (q + 2 * kv) + q * self.d_model + mlp

    @property
    def head_weights(self) -> int:
        return self.d_model * self.vocab

    def attn_flops(self, keys) -> float:
        """One query attending to `keys` positions, all layers: QK^T and
        PV."""
        return 4.0 * self.layers * self.heads * self.head_dim * keys

    def dense_flops_per_token(self) -> float:
        """Forward matmul operations of one token through every layer."""
        return 2.0 * self.layers * self.layer_weights

    def head_flops(self) -> float:
        return 2.0 * self.head_weights

    def prefill_flops(self, prompt_len: int) -> float:
        """A causal prefill of `prompt_len` tokens that emits the logits of
        its last position only (the first generated token)."""
        n = prompt_len
        causal_keys = n * (n + 1) / 2      # sum over positions of keys seen
        return (n * self.dense_flops_per_token()
                + 4.0 * self.layers * self.heads * self.head_dim * causal_keys
                + self.head_flops())

    def attention_flops(self, positions) -> float:
        """QK^T and PV of one decode step of rows whose new token sits at
        `positions` (each attends to position + 1 keys)."""
        return sum(self.attn_flops(p + 1) for p in positions)

    def attention_bytes(self, positions) -> int:
        """Least bytes the attention of one decode step reads: the K/V of
        each live row's real positions."""
        kv_per_pos = 2 * self.layers * self.kv_heads * self.head_dim
        return sum((p + 1) * kv_per_pos * self.kv_bytes for p in positions)

    def decode_flops(self, positions) -> float:
        """One decode step of rows whose new token sits at `positions`,
        logits for every row."""
        rows = len(positions)
        return (rows * (self.dense_flops_per_token() + self.head_flops())
                + self.attention_flops(positions))

    def decode_bytes(self, positions) -> int:
        """Least bytes one decode step reads: every weight once, and the
        K/V of each live row's real positions."""
        weights = self.layers * self.layer_weights + self.head_weights
        return weights * self.weight_bytes + self.attention_bytes(positions)

    def train_flops_per_token(self, seq_len: int) -> float:
        """Forward and backward operations per token of a causal sequence
        of `seq_len` (3x the forward; recomputation is not counted), with
        the loss's logits over the whole vocabulary at every position."""
        mean_keys = (seq_len + 1) / 2
        fwd = (self.dense_flops_per_token() + self.head_flops()
               + self.attn_flops(mean_keys))
        return 3.0 * fwd
