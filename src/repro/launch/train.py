"""Training launcher CLI.

  PYTHONPATH=src python -m repro.launch.train --arch phi4-mini-3.8b \
      --reduced --steps 200 --batch 8 --seq 128

--reduced trains the smoke-sized config on the host mesh (CPU-runnable);
full-size configs expect a real TPU fleet (the multi-pod dry-run is the
no-hardware proof path).

Matmul planning is session-scoped: --amp/--chip/--mm-backend/--plan-mode
push one mm_config layer over the whole run (see repro.core.config), so an
AMP sweep over a full training job is a CLI flag, not a code edit.
"""

from __future__ import annotations

import argparse


from repro.configs.base import get_config
from repro.core import config as mmcfg
from repro.data.pipeline import DataLoader, MemmapTokens, SyntheticLM
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import build_model
from repro.optim.adamw import AdamW
from repro.optim.schedule import warmup_cosine
from repro.train.train_step import TrainStepConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--data", default=None, help="memmap token file")
    ap.add_argument("--ckpt-dir", default="/tmp/repro-ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    mmcfg.add_cli_args(ap)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    bundle = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(model=args.model_parallel))

    opt = AdamW(lr=warmup_cosine(args.lr, args.warmup, args.steps))
    ts_cfg = TrainStepConfig(n_microbatches=args.microbatches,
                             loss_chunk=min(512, args.seq),
                             compress_grads=args.compress_grads)
    trainer = Trainer(bundle, opt, mesh, ts_cfg,
                      TrainerConfig(total_steps=args.steps,
                                    ckpt_every=args.ckpt_every,
                                    ckpt_dir=args.ckpt_dir))
    source = (MemmapTokens(args.data, cfg.vocab_size) if args.data
              else SyntheticLM(cfg.vocab_size))
    loader = DataLoader(source, args.batch, args.seq, mesh=mesh)
    try:
        with mmcfg.scope_from_args(args):
            out = trainer.run(loader)
    finally:
        loader.close()
    print(f"[train] done: final_loss={out['final_loss']}")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
