"""Trainer: composes step fn, data, checkpointing, and fault tolerance."""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable

import jax
from jax.sharding import PartitionSpec as P

from repro.checkpoint.ckpt import CheckpointManager
from repro.distributed import sharding as shd
from repro.distributed.fault_tolerance import StepGuard, retry_step
from repro.models.model import ModelBundle
from repro.optim.adamw import AdamW
from repro.train.train_step import (TrainState, TrainStepConfig,
                                    init_train_state, make_train_step)


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro-ckpt"
    ckpt_keep: int = 3
    log_every: int = 10
    seed: int = 0


class Trainer:
    def __init__(self, bundle: ModelBundle, opt: AdamW, mesh,
                 ts_cfg: TrainStepConfig = TrainStepConfig(),
                 cfg: TrainerConfig = TrainerConfig(),
                 log_fn: Callable[[str], None] = print):
        self.bundle, self.opt, self.mesh = bundle, opt, mesh
        self.ts_cfg, self.cfg, self.log = ts_cfg, cfg, log_fn
        self.ckpt = CheckpointManager(cfg.ckpt_dir, keep=cfg.ckpt_keep)
        self.guard = StepGuard()

        # The state is built by one jitted init that emits every leaf
        # already sharded: no device ever holds the whole unsharded state.
        key = jax.random.PRNGKey(cfg.seed)
        init = functools.partial(init_train_state, bundle, opt,
                                 ts_cfg=ts_cfg)
        self.state_specs = self._specs_for(jax.eval_shape(init, key))
        state_sh = shd.named(self.state_specs, mesh)
        self.state = jax.jit(init, out_shardings=state_sh)(key)
        step_fn = make_train_step(bundle, opt, ts_cfg)
        self.step_fn = jax.jit(step_fn, out_shardings=(state_sh, None))

    def _specs_for(self, state: TrainState) -> TrainState:
        p_specs = shd.tree_param_specs(state.params, self.mesh)
        mu_specs = shd.tree_optstate_specs(p_specs, state.opt.mu, self.mesh)
        opt_specs = type(state.opt)(step=P(), mu=mu_specs, nu=mu_specs)
        ef_specs = (None if state.ef is None else
                    type(state.ef)(residual=p_specs))
        return TrainState(params=p_specs, opt=opt_specs, ef=ef_specs,
                          rng=P())

    # ------------------------------------------------------------ resume
    def maybe_restore(self) -> int:
        step = self.ckpt.latest_step()
        if step is None:
            return 0
        self.state = self.ckpt.restore(self.state, step=step,
                                       specs=self.state_specs,
                                       mesh=self.mesh)
        self.log(f"[trainer] restored step {step} from {self.cfg.ckpt_dir}")
        return step

    # --------------------------------------------------------------- run
    def step(self, batch) -> dict:
        """One guarded optimizer step on `batch`; returns its metrics."""
        def one_step():
            return retry_step(self.step_fn, self.state, batch)

        (self.state, metrics), straggled = self.guard.run(one_step)
        if straggled:
            self.log("[trainer] straggler detected "
                     "(would re-form mesh on real fleet)")
        return metrics

    def run(self, loader) -> dict:
        start = self.maybe_restore()
        metrics_hist = []
        t0 = time.time()
        for step in range(start, self.cfg.total_steps):
            metrics = self.step(next(loader))
            if (step + 1) % self.cfg.log_every == 0 or step == start:
                loss = float(metrics["loss"])
                rate = (step + 1 - start) / (time.time() - t0)
                self.log(f"[trainer] step {step + 1} "
                         f"loss={loss:.4f} steps/s={rate:.2f}")
                metrics_hist.append((step + 1, loss))
            if (step + 1) % self.cfg.ckpt_every == 0:
                self.ckpt.save(int(step + 1), self.state)
        self.ckpt.save(self.cfg.total_steps, self.state, blocking=True)
        return {"history": metrics_hist,
                "final_loss": metrics_hist[-1][1] if metrics_hist else None}
