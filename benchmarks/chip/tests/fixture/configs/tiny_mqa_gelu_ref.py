"""Plain float32 reference of a multi-query decoder with a non-gated
tanh-GELU MLP and tied embeddings (the fixture `tiny-mqa-gelu`),
serving only.

It imports nothing of the program and takes nothing the program made.
Its weights are drawn from the seed by the recipe the configuration
states: the embedding N(0, 0.02) and every linear N(0, 1/fan_in), drawn
in float32 and stored in the configuration's dtype, norm gains as zero
offsets from 1; the key tree is the one a stacked, seeded init splits
(8 top-level keys, the layer keys split from the third; per layer the
attention's wq, wk, wv, wo, then the MLP's up and down).  Every matmul
runs at float32 with `Precision.HIGHEST`.

`control=True` also runs the control: every matmul operand scaled per
tensor into float8_e4m3fn and back, the precision below bfloat16.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0          # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    theta: float
    dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        if c["hidden_act"] != "gelu_pytorch_tanh":
            raise ValueError("the reference's GELU is the tanh form")
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   eps=c["rms_norm_eps"], theta=c["rope_theta"],
                   dtype=c["torch_dtype"])


# ------------------------------------------------------------- weights
def _linear(key, d_in, d_out, dtype):
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * (d_in ** -0.5)
    return w.astype(dtype).astype(jnp.float32)


def layer_keys(seed: int, arch: Arch):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    return jax.random.split(jax.random.split(keys[2], 1)[0], arch.layers)


@functools.partial(jax.jit, static_argnums=(1,))
def layer_weights(layer_key, arch: Arch) -> dict:
    block = jax.random.split(jax.random.split(layer_key, 1)[0], 4)
    ka = jax.random.split(block[0], 5)
    km = jax.random.split(block[1], 3)
    d, q, kv, dt = (arch.d, arch.heads * arch.head_dim,
                    arch.kv_heads * arch.head_dim, arch.dtype)
    return {"wq": _linear(ka[0], d, q, dt), "wk": _linear(ka[1], d, kv, dt),
            "wv": _linear(ka[2], d, kv, dt), "wo": _linear(ka[3], q, d, dt),
            "w_up": _linear(km[0], d, arch.ff, dt),
            "w_down": _linear(km[1], arch.ff, d, dt)}


@functools.partial(jax.jit, static_argnums=(1,))
def embedding(seed_key, arch: Arch):
    e = jax.random.normal(jax.random.split(seed_key, 8)[0],
                          (arch.vocab, arch.d), jnp.float32) * 0.02
    return e.astype(arch.dtype).astype(jnp.float32)


# ------------------------------------------------------------- forward
def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def dot(a, b, mode: str):
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HI)


def rmsnorm(x, eps):
    # the gains are zero offsets from 1, drawn as zeros
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, positions, theta):
    """x (B, T, H, hd): rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, :, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def layer(x, w, arch: Arch, mode: str):
    """One residual block over x (B, T, d), causal from position 0."""
    b, t, _ = x.shape
    hd, nh, nkv = arch.head_dim, arch.heads, arch.kv_heads
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    h = rmsnorm(x, arch.eps)
    q = rope(dot(h, w["wq"], mode).reshape(b, t, nh, hd), pos, arch.theta)
    k = rope(dot(h, w["wk"], mode).reshape(b, t, nkv, hd), pos, arch.theta)
    v = dot(h, w["wv"], mode).reshape(b, t, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI).reshape(b, t, -1)
    x = x + dot(ctx, w["wo"], mode)
    h = rmsnorm(x, arch.eps)
    up = jax.nn.gelu(dot(h, w["w_up"], mode), approximate=True)
    return x + dot(up, w["w_down"], mode)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, arch: Arch, mode: str):
    return layer(x, w, arch, mode)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _gaps(h, embed, idx, tokens, arch: Arch, mode: str):
    """Per picked position of the final hidden states h: the largest f32
    reference logit minus the reference logit of `tokens` (the served
    ones), or, with `mode="fp8"`, of the control's first choice."""
    pick = jnp.take_along_axis(h[mode], idx[..., None], axis=1)
    ref = dot(jnp.take_along_axis(h["f32"], idx[..., None], axis=1),
              embed.T, "f32")
    if mode == "fp8":
        tokens = dot(pick, embed.T, "fp8").argmax(-1)
    return ref.max(-1) - jnp.take_along_axis(ref, tokens[..., None], -1)[..., 0]


def serve_gaps(seed: int, arch: Arch, tokens, idx, served, *,
               control: bool = False):
    """tokens (B, T) int32, right-padded prompts + served tokens; idx
    (B, N) the positions whose next token was served; served (B, N)
    those tokens.  Returns (gap of each served token, gap of the
    control's first choice or None), both (B, N), in logits."""
    embed = embedding(jax.random.PRNGKey(seed), arch)
    weights = [layer_weights(k, arch) for k in layer_keys(seed, arch)]
    hidden = {}
    for mode in ("f32", "fp8") if control else ("f32",):
        x = jnp.take(embed, tokens, axis=0)
        for w in weights:
            x = _layer(x, w, arch, mode)
        hidden[mode] = rmsnorm(x, arch.eps)
    served_gap = _gaps(hidden, embed, idx, served, arch, "f32")
    if not control:
        return served_gap, None
    return served_gap, _gaps(hidden, embed, idx, served, arch, "fp8")
