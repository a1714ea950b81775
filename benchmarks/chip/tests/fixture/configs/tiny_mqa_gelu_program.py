"""The program config and work counts of a multi-query decoder with a
non-gated GELU MLP: a configuration of another architecture than the
default's, brought by its own file.  Its counts are the default's but
for the MLP, which holds two matrices (up, down) instead of three."""

from __future__ import annotations

import dataclasses

from chip import harness

_dense = harness.program({})


def model_config(c: dict, name: str):
    from repro.configs.base import ModelConfig

    if c["hidden_act"] != "gelu_pytorch_tanh":
        raise ValueError(f"{name}: the program's GELU is the tanh form")
    return ModelConfig(
        name=name, family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        mlp_type="gelu", rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"], norm_eps=c["rms_norm_eps"],
        dtype=c["torch_dtype"])


def dims(c: dict) -> "Dims":
    return Dims(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                heads=c["num_attention_heads"],
                kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"])


@dataclasses.dataclass(frozen=True)
class Dims(_dense.Dims):
    @property
    def layer_weights(self) -> int:
        """Matmul weights of one layer (attention + up and down)."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return (self.d_model * (q + 2 * kv) + q * self.d_model
                + self.mlp_weights)

    @property
    def mlp_weights(self) -> int:
        return 2 * self.d_model * self.d_ff

    def mlp_flops_per_token(self) -> float:
        return 2.0 * self.layers * self.mlp_weights
