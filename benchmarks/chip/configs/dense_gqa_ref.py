"""Plain float32 reference of a dense GQA decoder with SwiGLU MLPs and
tied embeddings (phi4-mini-3.8b and its 8-layer training stage).

It imports nothing of the program and takes nothing the program made.
Its weights are drawn from the seed by the same recipe the configuration
states (`assumed.weights` in the configuration file): the embedding
N(0, 0.02) and every linear N(0, 1/fan_in), drawn in float32 and stored
in the configuration's dtype, norm gains as zero offsets from 1; the key
tree is the one a stacked, seeded init splits (8 top-level keys, the
layer keys split from the third).  Every matmul runs at float32 with
`Precision.HIGHEST`, so nothing here is rounded below float32 except
weights stored as the configuration states.

`mode="fp8"` is the control: every matmul operand is scaled per tensor
into float8_e4m3fn and back before the float32 product, the precision
below the configuration's bfloat16 that a later change might be tempted
to take (the gradient of that rounding is taken as 1).
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
        if c.get("partial_rotary_factor", 1.0) != 1.0 or c.get("rope_scaling"):
            raise ValueError("the reference applies plain RoPE to whole heads")
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim",
                                  c["hidden_size"] // c["num_attention_heads"]),
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   eps=c["rms_norm_eps"], theta=c["rope_theta"],
                   dtype=c["torch_dtype"])


# ------------------------------------------------------------- weights
def _linear(key, d_in, d_out, dtype):
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * (d_in ** -0.5)
    return w.astype(dtype).astype(jnp.float32)


def layer_keys(seed: int, arch: Arch):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    stage = jax.random.split(keys[2], 1)[0]
    return jax.random.split(stage, arch.layers)


@functools.partial(jax.jit, static_argnums=(1,))
def layer_weights(layer_key, arch: Arch) -> dict:
    block = jax.random.split(jax.random.split(layer_key, 1)[0], 4)
    ka = jax.random.split(block[0], 5)
    km = jax.random.split(block[1], 3)
    d, q, kv, dt = (arch.d, arch.heads * arch.head_dim,
                    arch.kv_heads * arch.head_dim, arch.dtype)
    return {
        "ln1": jnp.zeros((d,), jnp.float32),
        "wq": _linear(ka[0], d, q, dt), "wk": _linear(ka[1], d, kv, dt),
        "wv": _linear(ka[2], d, kv, dt), "wo": _linear(ka[3], q, d, dt),
        "ln2": jnp.zeros((d,), jnp.float32),
        "w_gate": _linear(km[0], d, arch.ff, dt),
        "w_up": _linear(km[1], d, arch.ff, dt),
        "w_down": _linear(km[2], arch.ff, d, dt),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def embedding(seed_key, arch: Arch):
    keys = jax.random.split(seed_key, 8)
    e = jax.random.normal(keys[0], (arch.vocab, arch.d), jnp.float32) * 0.02
    return e.astype(arch.dtype).astype(jnp.float32)


def init_params(seed: int, arch: Arch) -> dict:
    """The whole model: per-layer weights stacked on a leading axis."""
    per = [layer_weights(k, arch) for k in layer_keys(seed, arch)]
    stacked = jax.tree.map(lambda *x: jnp.stack(x), *per)
    return {"embed": embedding(jax.random.PRNGKey(seed), arch),
            "final_norm": jnp.zeros((arch.d,), jnp.float32), **stacked}


# ------------------------------------------------------------- forward
def _fp8(x):
    """x rounded to float8_e4m3fn under a per-tensor scale.  The rounding
    is left out of the gradient (a straight-through estimator), so that
    the control's backward pass is its forward's, not a cotangent rounded
    to fp8 without a scale."""
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def dot(a, b, mode: str):
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


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
    h = rmsnorm(x, w["ln1"], arch.eps)
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
    h = rmsnorm(x, w["ln2"], arch.eps)
    g = dot(h, w["w_gate"], mode)
    return x + dot(jax.nn.silu(g) * dot(h, w["w_up"], mode), w["w_down"], mode)


def logits(h, final_norm, embed, arch: Arch, mode: str):
    return dot(rmsnorm(h, final_norm, arch.eps), embed.T, mode)


# ------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_rows(x, w, arch: Arch, mode: str):
    # one sequence at a time keeps the (H, T, T) scores of one row live
    return jax.lax.map(lambda r: layer(r[None], w, arch, mode)[0], x)


@functools.partial(jax.jit, static_argnums=(4,))
def _served_gap(h, embed, idx, tokens, arch: Arch):
    """Per picked position of the final hidden states h: the largest
    reference logit minus the reference logit of the served token."""
    ref = dot(jnp.take_along_axis(h, idx[..., None], axis=1), embed.T, "f32")
    served = jnp.take_along_axis(ref, tokens[..., None], -1)[..., 0]
    return ref.max(-1) - served


@functools.partial(jax.jit, static_argnums=(4,))
def _control_gap(h_ref, h_low, embed, idx, arch: Arch):
    """The same gap for the control's first choice, where the control's
    logits come from its own fp8 hidden states and head."""
    pick = lambda h: jnp.take_along_axis(h, idx[..., None], axis=1)
    ref = dot(pick(h_ref), embed.T, "f32")
    low = dot(pick(h_low), embed.T, "fp8")
    first = jnp.take_along_axis(ref, low.argmax(-1)[..., None], -1)[..., 0]
    return ref.max(-1) - first


def serve_gaps(seed: int, arch: Arch, tokens, idx, served, *,
               control: bool = False):
    """tokens (B, T) int32, right-padded sequences (prompt + served
    tokens); idx (B, N) the positions whose next token was served;
    served (B, N) those tokens.  Returns (gap of each served token, gap
    of the control's first choice, or None), both (B, N), in logits.
    The control runs the whole model in fp8 (`mode="fp8"`) on the same
    prompts and served tokens.

    The model is run layer by layer, each layer's weights drawn from the
    seed as it is needed, so that no more than one layer is held."""
    key = jax.random.PRNGKey(seed)
    embed = embedding(key, arch)
    zero = jnp.zeros((arch.d,), jnp.float32)
    hidden = {}
    for mode in ("f32", "fp8") if control else ("f32",):
        x = jnp.take(embed, tokens, axis=0)
        for k in layer_keys(seed, arch):
            x = _layer_rows(x, layer_weights(k, arch), arch, mode)
        hidden[mode] = rmsnorm(x, zero, arch.eps)
    g_served = _served_gap(hidden["f32"], embed, idx, served, arch)
    if not control:
        return g_served, None
    return g_served, _control_gap(hidden["f32"], hidden["fp8"], embed, idx,
                                  arch)


# ------------------------------------------------------------- training
def loss_fn(params, tokens, arch: Arch, mode: str = "f32", chunk: int = 256):
    """Mean next-token NLL over tokens (B, S)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    names = [k for k in params if k not in ("embed", "final_norm")]

    @jax.checkpoint
    def body(x, w):
        return layer(x, w, arch, mode), None

    x, _ = jax.lax.scan(body, x, {k: params[k] for k in names})
    h = rmsnorm(x[:, :-1], params["final_norm"], arch.eps)
    tgt = tokens[:, 1:]
    b, s, d = h.shape
    n = -(-s // chunk)
    pad = n * chunk - s
    hc = jnp.pad(h, ((0, 0), (0, pad), (0, 0))).reshape(b, n, chunk, d)
    tc = jnp.pad(tgt, ((0, 0), (0, pad))).reshape(b, n, chunk)
    mc = jnp.pad(jnp.ones_like(tgt, jnp.float32),
                 ((0, 0), (0, pad))).reshape(b, n, chunk)

    @jax.checkpoint
    def nll(carry, inp):
        hh, tt, mm = inp
        lg = dot(hh, params["embed"].T, mode)
        z = jax.scipy.special.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, tt[..., None], -1)[..., 0]
        return carry + jnp.sum((z - gold) * mm), None

    total, _ = jax.lax.scan(nll, jnp.zeros((), jnp.float32),
                            (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(tc, 1, 0),
                             jnp.moveaxis(mc, 1, 0)))
    return total / (b * s)


def adamw_step(params, mu, nu, step, grads, opt: dict, dtype: str):
    """AdamW with global-norm clipping; parameters are stored in the
    configuration's dtype after each update, moments in float32."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, m, v):
        u = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        u = u + opt["weight_decay"] * p
        return (p - opt["lr"] * u).astype(dtype).astype(jnp.float32)

    return jax.tree.map(upd, params, mu, nu), mu, nu, g
