"""Chip smoke test: the repository's main path, once, on TPU v5e.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # sharded training on a 2x2 v5e host

One chip:
  device  - the first device must be a TPU of a kind the planner models.
  kernels - `skewmm.matmul` under the Pallas backend in bf16 at real sizes
            (the 4096^3 square, the fig5 aspect-ratio sweeps, the
            phi4-mini LM head at decode) and each schedule forced once at
            >= 2 K-steps, against XLA's dot at highest precision.
  serve   - phi4-mini-3.8b at its published widths and all 32 layers
            (random weights from --seed) through `serve.sched.Scheduler`
            as `launch/serve_bench.py` drives it, once per matmul backend;
            prefill-then-decode logits are checked against a cache-less
            forward, and the Pallas run against the XLA run.
Four chips (--four-chips, only this phase):
  train   - `Trainer` steps of phi4-mini-3.8b cut to 8 layers on the
            `make_host_mesh(model=4)` mesh, a state no single chip holds;
            each device must hold about a quarter of it.  At 2 layers the
            first step's loss and grad norm on a 1-device mesh and on the
            4-device mesh must agree.

After every phase the guard ledger must show no fallback, scrub or caught
fault: on the chip a fallback is a failure, not a recovery.  Progress goes
to stdout; the last line is one JSON object naming the device.  Any failed
check exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

# jax.Device.device_kind -> the planner chip that models it.
DEVICE_KINDS = {"TPU v5 lite": "tpu_v5e", "TPU v5e": "tpu_v5e"}

ARCH = "phi4-mini-3.8b"

# Kernel outputs are fp32 accumulations of bf16 products, compared with
# XLA's fp32-accumulated dot.  Only the summation order differs, which
# moves a sum of K unit-variance terms by ~sqrt(K) * 2^-24 of its scale;
# 5e-4 of the reference RMS leaves > 10x headroom over that.  A kernel
# that rounded its accumulator or output to bf16 errs by >= 2^-9 of the
# largest outputs (~4 RMS), 4x past the bound; a stale or missing block
# errs by O(1).
KERNEL_TOL = 5e-4

# Logits of a bf16 model computed two ways (prefill+decode vs one
# cache-less forward; Pallas vs XLA kernels) differ by bf16 rounding of
# activations in a different order through the residual layers.  At
# these widths on the CPU the RMS difference was 0.6% of the reference
# RMS at 2 layers and 1.1% at 8, growing about as sqrt(depth): ~2.3% at
# 32.  Bounds, relative to the reference RMS: RMS difference <= 8% and
# largest difference <= 50%.  Activations rounded to 8 bits instead of
# bf16's 16 err ~16x more; a wrong cache entry, position or kernel block
# decorrelates a row and errs by ~140%.
LOGIT_RMS_TOL = 0.08
LOGIT_MAX_TOL = 0.5

# Loss and grad norm of the same first step on a 1-device and a 4-device
# mesh: the sharded program sums fp32 partials in another order, so the
# two agree to rounding; a wrong collective or sharding rule is O(1).
LOSS_RTOL = 2e-3
GRAD_NORM_RTOL = 2e-2

GUARD_COUNTERS = ("fallbacks", "scrubbed_batches", "faults_caught")


class SmokeFailure(Exception):
    """A check failed; the message says which."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def guard_clean(what: str) -> None:
    """No fault scope is armed, so any guard activity is a real failure."""
    from repro.guard import faults, health

    check(faults.active() is None, f"{what}: a fault scope is armed")
    snap = health.snapshot()
    bad = {k: snap[k] for k in GUARD_COUNTERS if snap.get(k)}
    if snap.get("fallback_level", 0) > 0:
        bad["fallback_level"] = snap["fallback_level"]
    check(not bad, f"{what}: guard ledger shows {bad}")
    log(f"[guard] {what}: clean")


@contextlib.contextmanager
def phase(name: str):
    """Log a phase's wall time on the host clock, compilation included."""
    t0 = time.perf_counter()
    yield
    log(f"[time] {name}: {time.perf_counter() - t0:.1f} s host wall clock, "
        f"compilation included")
    guard_clean(name)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ----------------------------------------------------------------- device
def device_phase(count: int) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: the first device is {dev.platform!r}")
    log(f"[device] {dev.platform} / {dev.device_kind} x{len(devices)}")
    check(dev.device_kind in DEVICE_KINDS,
          f"unknown device_kind {dev.device_kind!r}; the planner models "
          f"{sorted(DEVICE_KINDS)}")
    check(len(devices) >= count,
          f"{count} devices needed, JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------- kernels
def kernel_cases(chip: str) -> list:
    """(name, m, k, n, plan) — plan None lets the planner choose."""
    from repro.core.config import mm_config
    from repro.core.costmodel import BlockPlan
    from repro.core.planner import sweep_aspect_ratios

    cases = [("square", 4096, 4096, 4096, None)]
    ratios = [2.0**i for i in range(-8, 9, 2)]
    with mm_config(chip=chip):
        for vary, tag in (("a_aspect", "skew"), ("output", "oskew")):
            for r in sweep_aspect_ratios(4096 * 4096, ratios, vary=vary):
                cases.append((f"{tag}{r['ratio']:g}", r["m"], r["k"], r["n"],
                              None))
    cases += [(f"decode_m{m}", m, 3072, 200064, None) for m in (1, 8)]
    # Each schedule forced at 4 K-steps, and at >= 2 inner blocks so the
    # resident schedules revisit every output block.
    cases += [
        ("forced_k_inner", 1024, 4096, 2048,
         BlockPlan(256, 1024, 512, schedule="k_inner")),
        ("forced_a_resident", 64, 4096, 8192,
         BlockPlan(64, 1024, 1024, schedule="a_resident")),
        ("forced_b_resident", 8192, 4096, 64,
         BlockPlan(1024, 1024, 128, schedule="b_resident")),
        ("forced_splitk", 8, 4096, 8192,
         BlockPlan(8, 1024, 2048, schedule="splitk")),
    ]
    return cases


def kernel_phase(chip: str, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import skewmm
    from repro.core.config import mm_config
    from repro.kernels import ops

    @jax.jit
    def error_and_scale(a, b, out):
        with jax.default_matmul_precision("highest"):
            ref = jnp.dot(a, b, preferred_element_type=jnp.float32)
        return (jnp.max(jnp.abs(out - ref)),
                jnp.sqrt(jnp.mean(jnp.square(ref))))

    key = jax.random.PRNGKey(seed)
    for i, (name, m, k, n, plan) in enumerate(kernel_cases(chip)):
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        a = jax.random.normal(ka, (m, k), jnp.bfloat16)
        b = jax.random.normal(kb, (k, n), jnp.bfloat16)
        with mm_config(backend="pallas", chip=chip, plan_mode="skew_aware"):
            if plan is None:
                with skewmm.plan_capture() as caps:
                    lowered = jax.jit(lambda x, y: skewmm.matmul(
                        x, y, out_dtype=jnp.float32)).lower(a, b)
                plan = caps[0].plan
            else:
                lowered = jax.jit(lambda x, y, p=plan: ops.skew_matmul(
                    x, y, plan=p, out_dtype=jnp.float32)).lower(a, b)
            compiled = lowered.compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"kernel {name}: no Pallas kernel in the compiled program")
        err, scale = (float(x) for x in
                      error_and_scale(a, b, compiled(a, b)))
        rel = err / scale
        gk = -(-k // plan.bk)
        log(f"[kernels] {name} ({m},{k},{n}) {plan.schedule} "
            f"({plan.bm},{plan.bk},{plan.bn}) gk={gk}: "
            f"max|err|/rms = {rel:.3e}")
        check(rel <= KERNEL_TOL,
              f"kernel {name}: max|err|/rms {rel:.3e} > {KERNEL_TOL}")


# ------------------------------------------------------------------ serve
def _logit_error(got, ref) -> tuple[float, float]:
    import numpy as np

    diff = np.asarray(got, np.float64) - np.asarray(ref, np.float64)
    rms = float(np.sqrt(np.mean(np.square(np.asarray(ref, np.float64)))))
    return (float(np.sqrt(np.mean(np.square(diff)))) / rms,
            float(np.max(np.abs(diff))) / rms)


def _check_logits(what: str, got, ref) -> None:
    rms_rel, max_rel = _logit_error(got, ref)
    log(f"[serve] {what}: rms {rms_rel:.3e}, max {max_rel:.3e} "
        f"(of reference RMS)")
    check(rms_rel <= LOGIT_RMS_TOL and max_rel <= LOGIT_MAX_TOL,
          f"{what}: logits differ by rms {rms_rel:.3e} / max "
          f"{max_rel:.3e} of reference RMS (bounds {LOGIT_RMS_TOL} / "
          f"{LOGIT_MAX_TOL})")


def cacheless_logits(params, cfg, trace, results) -> dict:
    """Logits of one cache-less forward over each request's prompt plus
    its generated tokens, at every position the scheduler sampled."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.config import mm_config
    from repro.models import transformer

    seqs = {r.rid: list(r.tokens) + list(results[r.rid]["tokens"][:-1])
            for r in trace}
    width = max(len(s) for s in seqs.values())
    rids = sorted(seqs)
    tokens = np.zeros((len(rids), width), np.int32)
    for row, rid in enumerate(rids):
        tokens[row, :len(seqs[rid])] = seqs[rid]
    prompt = {r.rid: len(r.tokens) for r in trace}
    n_out = max(len(results[rid]["tokens"]) for rid in rids)
    index = np.zeros((len(rids), n_out), np.int32)
    for row, rid in enumerate(rids):
        first = prompt[rid] - 1
        index[row] = np.minimum(np.arange(first, first + n_out), width - 1)

    def forward(p, t, idx):
        # right padding is invisible to the causal positions compared
        h, _ = transformer.forward_hidden(p, cfg, t)
        h = jnp.take_along_axis(h, idx[..., None], axis=1)
        return transformer.unembed(p, cfg, h)

    with mm_config(backend="xla"):
        out = np.asarray(jax.jit(forward)(params, jnp.asarray(tokens),
                                          jnp.asarray(index)))
    return {rid: out[row, :len(results[rid]["tokens"])]
            for row, rid in enumerate(rids)}


def serve_phase(cfg, seed: int, *, chip: str, entries, max_new: int,
                backends=("xla", "pallas")) -> None:
    """Serve `entries` ((arrival, prompt_len, max_new) per request) once
    per backend and check every run."""
    import jax
    import numpy as np

    from repro.core import config as mmcfg
    from repro.guard import health
    from repro.models.model import build_model
    from repro.serve.sched import (BucketTable, Scheduler, assert_covered,
                                   build_tuned_cache, capture_gemm_specs,
                                   scripted_trace)
    from repro.tune import runtime as tune_runtime

    device = jax.devices()[0]
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {n_bytes / 1e9:.2f} GB of {cfg.dtype} weights; "
        f"peak_bytes_in_use {peak_bytes(device)}")

    table = BucketTable.for_workload(
        max_batch=len(entries), max_prompt=max(p for _, p, _ in entries),
        max_new=max_new, min_prompt=64)
    trace = scripted_trace(entries, vocab_size=cfg.vocab_size, seed=seed)
    with mmcfg.mm_config(chip=chip):
        # the tuned cache serve_bench builds: modeled, in memory
        specs = capture_gemm_specs(params, cfg, table)
        cache = build_tuned_cache(params, cfg, table)
        assert_covered(cache, specs)
    runs = {}
    for backend in backends:
        health.reset()
        with mmcfg.mm_config(backend=backend, chip=chip, plan_mode="tuned"), \
                tune_runtime.use_cache(cache):
            sched = Scheduler(params, cfg, table, trace_logits=True)
            results = sched.run(trace, max_ticks=64)
        snap = health.snapshot()
        log(f"[serve] {backend}: {len(results)}/{len(trace)} requests, "
            f"{sched.telemetry.decode_steps} decode steps, "
            f"{snap.get('tuned_hits', 0)} tuned hits / "
            f"{snap.get('tuned_misses', 0)} misses; peak_bytes_in_use "
            f"{peak_bytes(device)}")
        check(len(results) == len(trace),
              f"serve {backend}: {len(trace) - len(results)} requests did "
              f"not complete")
        check(not snap.get("tuned_misses"),
              f"serve {backend}: tuned lookups missed")
        for rid, res in results.items():
            check(len(res["tokens"]) == trace[rid].max_new,
                  f"serve {backend}: request {rid} produced "
                  f"{len(res['tokens'])} of {trace[rid].max_new} tokens")
        got = {rid: np.stack(sched.logit_trace[rid]) for rid in results}
        ref = cacheless_logits(params, cfg, trace, results)
        _check_logits(f"{backend} prefill+decode vs cache-less forward",
                      np.concatenate([got[r] for r in sorted(got)]),
                      np.concatenate([ref[r] for r in sorted(ref)]))
        guard_clean(f"serve {backend}")
        runs[backend] = (results, got)

    if len(runs) == 2:
        # Compare positions whose inputs agree: a rounding difference
        # may flip a sampled token and fork the two runs after it.
        (res_a, log_a), (res_b, log_b) = runs.values()
        rows_a, rows_b = [], []
        for rid in sorted(res_a):
            ta, tb = res_a[rid]["tokens"], res_b[rid]["tokens"]
            same = 1
            while same < len(ta) and ta[same - 1] == tb[same - 1]:
                same += 1
            rows_a.append(log_a[rid][:same])
            rows_b.append(log_b[rid][:same])
        n = sum(len(r) for r in rows_a)
        log(f"[serve] pallas vs xla over {n} positions with equal inputs")
        _check_logits("pallas vs xla", np.concatenate(rows_b),
                      np.concatenate(rows_a))


# ------------------------------------------------------------------ train
def _make_trainer(cfg, mesh, ckpt_dir: str, seed: int):
    from repro.models.model import build_model
    from repro.optim.adamw import AdamW
    from repro.train.train_step import TrainStepConfig
    from repro.train.trainer import Trainer, TrainerConfig

    return Trainer(build_model(cfg), AdamW(lr=1e-4), mesh,
                   TrainStepConfig(loss_chunk=256),
                   TrainerConfig(total_steps=1, ckpt_dir=ckpt_dir, seed=seed),
                   log_fn=log)


def _shard_batch(tokens, mesh) -> dict:
    import jax
    from jax.sharding import NamedSharding

    from repro.distributed.sharding import batch_spec

    return {"tokens": jax.device_put(tokens, NamedSharding(
        mesh, batch_spec(tokens.shape, mesh)))}


def _loss_and_grad_norm(trainer, batch) -> tuple[float, float]:
    """The first step's loss and global grad norm, as the train step
    computes them, without the optimizer update: on one device the old
    and the new AdamW state of phi4-mini do not fit together."""
    import jax
    import jax.numpy as jnp

    from repro.train.train_step import make_loss_fn

    grad_fn = jax.value_and_grad(make_loss_fn(trainer.bundle, trainer.ts_cfg))

    def measure(params, batch):
        loss, grads = grad_fn(params, batch)
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree.leaves(grads))
        return loss, jnp.sqrt(sq)

    loss, gnorm = jax.jit(measure)(trainer.state.params, batch)
    return float(loss), float(gnorm)


def _first_step(cfg, mesh, tokens, seed: int, *, step: bool):
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        trainer = _make_trainer(cfg, mesh, ckpt, seed)
        check(trainer.maybe_restore() == 0,
              "a fresh checkpoint directory restored a step")
        batch = _shard_batch(tokens, mesh)
        measured = _loss_and_grad_norm(trainer, batch)
        if step:
            # the measurement above is the trainer's own first step
            metrics = trainer.step(batch)
            stepped = (float(metrics["loss"]), float(metrics["grad_norm"]))
            log(f"[train] {mesh.devices.size}-device Trainer.step: loss "
                f"{stepped[0]!r} grad_norm {stepped[1]!r}")
            _agree("measured vs Trainer.step", measured, stepped)
        return measured


def _agree(what: str, a, b) -> None:
    check(abs(a[0] - b[0]) <= LOSS_RTOL * abs(a[0]),
          f"{what}: loss {a[0]!r} vs {b[0]!r}")
    check(abs(a[1] - b[1]) <= GRAD_NORM_RTOL * abs(a[1]),
          f"{what}: grad norm {a[1]!r} vs {b[1]!r}")


def _state_bytes_per_device(state) -> dict:
    import jax

    per = {}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            per[shard.device] = per.get(shard.device, 0) + shard.data.nbytes
    return per


def train_phase(cfg, seed: int, *, layers: int, compare_layers: int,
                batch: int, seq: int, steps: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.data.pipeline import DataLoader, SyntheticLM
    from repro.launch.mesh import make_host_mesh

    devices = jax.devices()
    mesh4 = make_host_mesh(model=4)
    check(mesh4.devices.size == 4, f"mesh holds {mesh4.devices.size} devices")
    mesh1 = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))

    small = dataclasses.replace(cfg, n_layers=compare_layers)
    tokens = SyntheticLM(cfg.vocab_size, seed=seed).batch(0, batch, seq)
    one = _first_step(small, mesh1, tokens, seed, step=False)
    four = _first_step(small, mesh4, tokens, seed, step=True)
    log(f"[train] {compare_layers} layers, first step: 1 device loss "
        f"{one[0]!r} grad_norm {one[1]!r}; 4 devices loss {four[0]!r} "
        f"grad_norm {four[1]!r}")
    _agree("1- vs 4-device first step", one, four)
    guard_clean("train 1-vs-4")

    deep = dataclasses.replace(cfg, n_layers=layers)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        trainer = _make_trainer(deep, mesh4, ckpt, seed)
        check(trainer.maybe_restore() == 0,
              "a fresh checkpoint directory restored a step")
        n_params = sum(x.size for x in jax.tree.leaves(trainer.state.params))
        loader = DataLoader(SyntheticLM(cfg.vocab_size, seed=seed), batch,
                            seq, mesh=mesh4)
        try:
            for step in range(steps):
                metrics = trainer.step(next(loader))
                loss = float(metrics["loss"])
                log(f"[train] {layers} layers, step {step + 1}: loss "
                    f"{loss!r} grad_norm {float(metrics['grad_norm'])!r}")
                check(np.isfinite(loss), f"step {step + 1}: loss {loss}")
        finally:
            loader.close()
        per = _state_bytes_per_device(trainer.state)
        total = sum(per.values())
        log(f"[train] {n_params / 1e9:.3f} B parameters; state "
            f"{total / 1e9:.2f} GB over {len(per)} devices")
        for dev in sorted(per, key=lambda d: d.id):
            stats = dev.memory_stats() or {}
            log(f"[train] device {dev.id}: state {per[dev] / 1e9:.3f} GB "
                f"({per[dev] / total:.3f} of it), bytes_in_use "
                f"{stats.get('bytes_in_use')}, peak_bytes_in_use "
                f"{stats.get('peak_bytes_in_use')}")
        check(len(per) == 4, f"state lives on {len(per)} devices, not 4")
        for dev, nbytes in per.items():
            check(0.2 <= nbytes / total <= 0.3,
                  f"device {dev.id} holds {nbytes / total:.3f} of the state")
            in_use = (dev.memory_stats() or {}).get("bytes_in_use")
            check(in_use is None or in_use < 0.5 * total,
                  f"device {dev.id} has {in_use} B in use, half or more "
                  f"of the whole state")
    guard_clean(f"train {layers}-layer")


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache

    log(f"[cache] compilation cache: {compile_cache.enable()}")

    from repro.configs.base import get_config
    from repro.tune import cache as tune_cache
    from repro.tune import runtime as tune_runtime

    # Plans come from the planner and an in-memory tuned cache only: no
    # tune cache left on disk is ever read.
    tune_runtime.set_active_cache(tune_cache.TuneCache())
    cfg = get_config(ARCH)
    try:
        device = device_phase(4 if args.four_chips else 1)
        chip = DEVICE_KINDS[device["kind"]]
        guard_clean("device")
        if args.four_chips:
            with phase("train"):
                train_phase(cfg, args.seed, layers=8, compare_layers=2,
                            batch=4, seq=512, steps=3)
        else:
            with phase("kernels"):
                kernel_phase(chip, args.seed)
            with phase("serve"):
                serve_phase(cfg, args.seed, chip=chip,
                            entries=[(0, 100, 8), (0, 400, 8), (1, 96, 8),
                                     (1, 384, 8)],
                            max_new=8)
    except SmokeFailure as e:
        print(f"[FAIL] {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
