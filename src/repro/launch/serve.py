"""Serving launcher: batched prefill + decode loop on the host mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-27b --reduced \
      --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import config as mmcfg
from repro.models.model import build_model
from repro.serve import encdec_engine, engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    mmcfg.add_cli_args(ap)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.gen
    rng = np.random.default_rng(0)
    toks = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)

    # One mm_config layer over prefill + every decode trace: the serving
    # session's planning knobs are set once, not threaded per call.
    with mmcfg.scope_from_args(args):
        if cfg.family == "encdec":
            frames = jnp.asarray(
                rng.normal(size=(args.batch, cfg.frontend_len, cfg.d_model)),
                jnp.float32)
            cache, logits = encdec_engine.prefill(params, cfg, frames, toks,
                                                  max_len=max_len)
            step = jax.jit(lambda c, t, p: encdec_engine.decode_step(
                params, cfg, c, t, p))
        else:
            cache, logits = engine.prefill(params, cfg, toks,
                                           max_len=max_len)
            step = jax.jit(lambda c, t, p: engine.decode_step(
                params, cfg, c, t, p))

        key = jax.random.PRNGKey(1)
        out_tokens = []
        t0 = time.time()
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for i in range(args.gen):
            out_tokens.append(np.asarray(tok))
            logits, cache = step(cache, tok,
                                 jnp.asarray(args.prompt_len + i, jnp.int32))
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits / args.temperature, -1).astype(jnp.int32)
        dt = time.time() - t0
    gen = np.stack(out_tokens, 1)
    print(f"[serve] generated {gen.shape} tokens in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(gen[:, :16])


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
