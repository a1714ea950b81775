"""Training cells: the program's `Trainer.step` on `make_host_mesh(model=4)`
fed by its `DataLoader`, with batches from the mix's generator.

Set-up builds the trainer (its state made sharded on the devices from the
seed) and drives it through its first three steps, which compile the
step and give the correctness readings; the same trainer then runs the
window.  Each step of the window ends when its loss is on the host.

After the window the program's readings are compared with the plain
reference's three steps on the same batches:
  loss_rel_gap     the largest relative gap of a step's loss;
  grad_norm_gap    over leaves, the gap between the program's and the
                   reference's norm of the first (clipped) gradient, as
                   the optimizer got it (the program's is read back from
                   its first moment after one step), over the larger of
                   the reference leaf's norm and the median leaf's;
  change_norm_gap  the same for the norm of each leaf's change over the
                   three steps, leaving out leaves whose reference
                   gradient is under a thousandth of the median leaf's.
"""

from __future__ import annotations

import gc
import tempfile
import time

import numpy as np

from chip import harness, traffic

# Leaves whose reference gradient norm is below this share of the median
# leaf's move by round-off alone and are left out of the change.
STILL_LEAF = 1e-3


def leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "name", path[-1])))


def leaf_norms(tree, scale: float = 1.0) -> dict:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))(
        tree)
    flat = jax.tree_util.tree_flatten_with_path(norms)[0]
    return {leaf_name(p): float(v) * scale for p, v in flat}


def diff_norms(a, b) -> dict:
    import jax
    import jax.numpy as jnp

    d = jax.jit(lambda x, y: jax.tree.map(
        lambda u, v: jnp.sqrt(jnp.sum(jnp.square(
            u.astype(jnp.float32) - v.astype(jnp.float32)))), x, y))(a, b)
    return {leaf_name(p): float(v)
            for p, v in jax.tree_util.tree_flatten_with_path(d)[0]}


def run(ctx, t0: float) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataLoader
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import build_model
    from repro.optim.adamw import AdamW
    from repro.train.train_step import TrainStepConfig
    from repro.train.trainer import Trainer, TrainerConfig

    c, mix = ctx.config, ctx.mix
    cfg = harness.program_config(c, ctx.cell["config"])
    opt_cfg = c["optimizer"]
    batch, seq = mix["batch"], mix["seq_len"]
    source = traffic.TrainBatches(mix, ctx.seed, cfg.vocab_size)
    mesh = make_host_mesh(model=ctx.cell["chips"])
    if mesh.devices.size != ctx.cell["chips"]:
        raise harness.Refused(f"mesh of {mesh.devices.size} devices")
    opt = AdamW(lr=opt_cfg["lr"], b1=opt_cfg["b1"], b2=opt_cfg["b2"],
                eps=opt_cfg["eps"], weight_decay=opt_cfg["weight_decay"],
                grad_clip=opt_cfg["grad_clip"])
    ckpt = tempfile.TemporaryDirectory(prefix="chipbench_ckpt_")
    trainer = Trainer(build_model(cfg), opt, mesh,
                      TrainStepConfig(loss_chunk=c["loss_chunk"]),
                      TrainerConfig(seed=traffic.seed32(ctx.seed),
                                    ckpt_dir=ckpt.name),
                      log_fn=lambda msg: None)
    loader = DataLoader(source, batch, seq, mesh=mesh)
    try:
        # the first three steps: compile, and the correctness readings
        p0 = jax.tree.map(jnp.copy, trainer.state.params)
        losses = []
        for i in range(3):
            m = trainer.step(next(loader))
            losses.append(float(m["loss"]))
            if i == 0:
                grads = leaf_norms(trainer.state.opt.mu, 1.0 / (1 - opt.b1))
        change = diff_norms(trainer.state.params, p0)
        del p0
        readings = {"loss": losses, "grad": grads, "change": change}

        counters = ctx.compile_counter()
        trace_dir = ctx.start_trace()
        t_open = time.perf_counter()
        deadline = t_open + ctx.seconds
        steps = []
        t = t_open
        while t < deadline:
            s0 = time.perf_counter()
            with harness.annotate("bench.loader", ctx.trace):
                b = next(loader)
            with harness.annotate("bench.step", ctx.trace,
                                  step_num=len(steps)):
                m = trainer.step(b)
                jax.block_until_ready(m["loss"])
            t = time.perf_counter()
            steps.append({"t0": s0, "t1": t})
        t_close = t
        trace_path = ctx.stop_trace(trace_dir)
        counts = counters.read()
        memory_peak = ctx.memory_peak()
    finally:
        loader.close()
    del trainer, b, m
    gc.collect()
    ckpt.cleanup()

    window = t_close - t_open
    rec = {"kind": "train", "setup_s": t_open - t0, "window_s": window,
           "train_tokens": len(steps) * batch * seq, "steps": steps,
           "step_span": "bench.step", "seq_len": seq,
           "chips": ctx.cell["chips"], "compile_counts": counts,
           "dims": ctx.dims, "peaks": ctx.peaks,
           "attempted": len(steps) + 3, "failed": 0,
           "memory_peak_bytes": memory_peak,
           "notes": [f"window {window:.3f} s: {len(steps)} steps of "
                     f"{batch} x {seq} tokens",
                     f"compile events in the window: {counts}",
                     f"program losses {losses}"]}
    if trace_path:
        rec["trace_path"] = trace_path
    ref = reference_readings(ctx, source, batch, seq, mesh)
    rec["checks"] = compare(readings, ref, ctx.limits)
    rec["notes"].append(f"reference losses {ref['loss']}")
    if ctx.control:
        rec["control"] = {"program": gaps(readings, ref)}
        for what, kw in (("control_fp8", {"mode": "fp8"}),
                         ("fault_half_batch", {"fault": "half_batch"})):
            low = reference_readings(ctx, source, batch, seq, mesh, **kw)
            rec["control"][what] = gaps(low, ref)
        rec["control"]["fault_state_unchanged"] = gaps(
            {"loss": ref["loss"], "grad": ref["grad"],
             "change": {k: 0.0 for k in ref["change"]}}, ref)
    return rec


def reference_readings(ctx, source, batch, seq, mesh, mode: str = "f32",
                       fault: str | None = None) -> dict:
    """The plain reference's three steps from the same seed and batches.
    Its state is placed over the mesh so that it fits; `mode="fp8"` is
    the control; `fault` plants one of the faults the check must catch
    ("half_batch": the loss of half the rows only)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    ref = harness.reference(ctx.config, ctx.root)
    arch = ref.Arch.from_config(ctx.config)
    opt = ctx.config["optimizer"]
    axis = mesh.axis_names[-1]
    n = mesh.shape[axis]

    def spec(x):
        if x.ndim >= 2 and x.shape[-1] % n == 0 and x.ndim == 3:
            return NamedSharding(mesh, P(None, None, axis))
        if x.ndim == 2 and x.shape[0] % n == 0:
            return NamedSharding(mesh, P(axis, None))
        return NamedSharding(mesh, P())

    seed = traffic.seed32(ctx.seed)
    shapes = jax.eval_shape(lambda: ref.init_params(seed, arch))
    shard = jax.tree.map(spec, shapes)
    params = jax.jit(lambda: ref.init_params(seed, arch),
                     out_shardings=shard)()
    chunk = ctx.config["loss_chunk"]
    rows = ctx.mix.get("reference_rows", batch)
    grad = jax.jit(jax.value_and_grad(
        lambda p, tok: ref.loss_fn(p, tok, arch, mode, chunk)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=shard)
    mu, nu = zeros(params), zeros(params)
    # s = (step, 1 / blocks): the block gradients' sum becomes their mean
    upd = jax.jit(lambda p, m, v, s, g: ref.adamw_step(
        p, m, v, s[0], jax.tree.map(lambda x: x * s[1], g), opt,
        arch.dtype),
        out_shardings=(shard,) * 4)
    p0 = jax.tree.map(jnp.copy, params)
    losses = []
    for step in range(3):
        tok = source.batch(step, batch, seq)
        if fault == "half_batch":
            tok = tok[: max(1, batch // 2)]
        # the mean over all rows, as a sum over blocks of `rows` rows
        value, g = 0.0, None
        blocks = range(0, tok.shape[0], rows)
        for r in blocks:
            v, gb = grad(params, jnp.asarray(tok[r:r + rows]))
            value += float(v)
            g = gb if g is None else add(g, gb)
            del gb
        scale = jnp.asarray([step + 1, 1.0 / len(blocks)], jnp.float32)
        params, mu, nu, clipped = upd(params, mu, nu, scale, g)
        losses.append(value / len(blocks))
        if step == 0:
            grads = leaf_norms(clipped)
        del g, clipped
    change = diff_norms(params, p0)
    return {"loss": losses, "grad": grads, "change": change}


def _worst_leaf(prog: dict, ref: dict, keep) -> float:
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def gaps(prog: dict, ref: dict) -> dict:
    """The three compared numbers of a run's readings."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise KeyError(f"leaves differ: {sorted(prog['grad'])} vs "
                       f"{sorted(ref['grad'])}")
    leaves = sorted(ref["grad"])
    med_grad = float(np.median([ref["grad"][k] for k in leaves]))
    moving = [k for k in leaves if ref["grad"][k] >= STILL_LEAF * med_grad]
    return {
        "loss_rel_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(prog["loss"], ref["loss"])),
        "grad_norm_gap": _worst_leaf(prog["grad"], ref["grad"], leaves),
        "change_norm_gap": _worst_leaf(prog["change"], ref["change"], moving),
    }


def compare(prog: dict, ref: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits[k], "pass_if": "<="}
            for k, v in gaps(prog, ref).items()}
