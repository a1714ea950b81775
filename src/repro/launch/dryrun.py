import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step / prefill /
decode_step), gives every input a ShapeDtypeStruct stand-in with its
production sharding, compiles for the 16x16 (single-pod) and 2x16x16
(multi-pod) meshes, and extracts:

  * compiled.memory_analysis()  — bytes/device (proves it fits)
  * compiled.cost_analysis()    — per-device HLO FLOPs/bytes
  * collective bytes parsed from the HLO text

into a roofline JSON under results/dryrun/.  Failures here are sharding
bugs by definition (see the brief).

Usage:
  python -m repro.launch.dryrun --arch gemma2-27b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import all_arch_ids, get_config
from repro.core import config as mmcfg
from repro.core import roofline
from repro.distributed import sharding as shd
from repro.launch import shapes as shapes_mod
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model, model_flops, param_shapes
from repro.optim.adamw import AdamW
from repro.serve import encdec_engine, engine, kvcache
from repro.train.train_step import (TrainState, TrainStepConfig,
                                    make_train_step)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _tree_sds(shapes, specs, mesh):
    return jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, mesh, sp), shapes, specs)


def input_specs(arch: str, shape_name: str, mesh):
    """ShapeDtypeStruct stand-ins (weak-type-correct, sharded, no
    allocation) for every model input of the cell."""
    cfg = get_config(arch)
    cell = shapes_mod.SHAPES[shape_name]
    b, s = cell.global_batch, cell.seq_len
    batch = {"tokens": _sds((b, s), jnp.int32, mesh,
                            shd.batch_spec((b, s), mesh))}
    if cfg.family == "vlm" and cell.mode != "decode":
        fshape = (b, cfg.frontend_len, cfg.d_model)
        batch["prefix_embeds"] = _sds(fshape, jnp.bfloat16, mesh,
                                      shd.batch_spec(fshape, mesh))
    if cfg.family == "encdec" and cell.mode != "decode":
        fshape = (b, min(cfg.frontend_len, s), cfg.d_model)
        batch["frames"] = _sds(fshape, jnp.bfloat16, mesh,
                               shd.batch_spec(fshape, mesh))
    return batch


FSDP_PARAM_THRESHOLD = 60e9   # >60B params: TP alone can't fit v5e HBM


def _use_fsdp(cfg) -> bool:
    from repro.models.model import count_params_active
    total, _ = count_params_active(cfg)
    return total > FSDP_PARAM_THRESHOLD


def _param_sds(cfg, mesh):
    shapes = param_shapes(cfg)
    specs = shd.tree_param_specs(shapes, mesh, fsdp=_use_fsdp(cfg))
    return _tree_sds(shapes, specs, mesh), specs


def lower_cell(arch: str, shape_name: str, mesh_kind: str):
    multi = mesh_kind == "multipod"
    mesh = make_production_mesh(multi_pod=multi)
    shd.set_annotation_mesh(mesh)
    chips = int(np.prod(list(mesh.shape.values())))
    cfg = get_config(arch)
    cell = shapes_mod.SHAPES[shape_name]
    bundle = build_model(cfg)
    batch_sds = input_specs(arch, shape_name, mesh)
    p_sds, p_specs = _param_sds(cfg, mesh)

    if cell.mode == "train":
        opt = AdamW(lr=3e-4)
        ts_cfg = TrainStepConfig(
            n_microbatches=shapes_mod.microbatches_for(cfg, cell),
            loss_chunk=512)
        step_fn = make_train_step(bundle, opt, ts_cfg)
        opt_sds = jax.eval_shape(opt.init, p_sds)
        mu_specs = shd.tree_optstate_specs(p_specs, opt_sds.mu, mesh)
        opt_specs = type(opt_sds)(step=P(), mu=mu_specs, nu=mu_specs)
        opt_sds = _tree_sds(opt_sds, opt_specs, mesh)
        rng_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
        state_sds = TrainState(params=p_sds, opt=opt_sds, ef=None,
                               rng=rng_sds)
        state_specs = TrainState(params=p_specs, opt=opt_specs, ef=None,
                                 rng=P())
        out_shardings = (
            jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                         is_leaf=lambda x: isinstance(x, P)),
            None)
        fn = jax.jit(step_fn, out_shardings=out_shardings)
        lowered = fn.lower(state_sds, batch_sds)
        n_tokens = cell.global_batch * cell.seq_len
        mflops = model_flops(cfg, tokens=n_tokens, mode="train")

    elif cell.mode == "prefill":
        max_len = cell.seq_len
        if cfg.family == "encdec":
            def fn(params, batch):
                return encdec_engine.prefill(params, cfg, batch["frames"],
                                             batch["tokens"],
                                             max_len=max_len)
        else:
            def fn(params, batch):
                return engine.prefill(params, cfg, batch["tokens"],
                                      max_len=max_len,
                                      prefix_embeds=batch.get(
                                          "prefix_embeds"))
        lowered = jax.jit(fn).lower(p_sds, batch_sds)
        n_tokens = cell.global_batch * cell.seq_len
        mflops = model_flops(cfg, tokens=n_tokens, mode="serve")

    else:  # decode
        b = cell.global_batch
        tok_sds = _sds((b,), jnp.int32, mesh, shd.batch_spec((b,), mesh))
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        if cfg.family == "encdec":
            cache_shapes = jax.eval_shape(
                lambda: encdec_engine.init_cache(
                    cfg, b, cell.seq_len,
                    enc_len=min(cfg.frontend_len, cell.seq_len)))
            cache_specs = shd.tree_cache_specs(cache_shapes, mesh)
            cache_sds = _tree_sds(cache_shapes, cache_specs, mesh)

            def fn(params, cache, tok, pos):
                return encdec_engine.decode_step(params, cfg, cache, tok,
                                                 pos)
        else:
            cache_shapes = jax.eval_shape(
                lambda: kvcache.init_cache(cfg, b, cell.seq_len))
            cache_specs = shd.tree_cache_specs(cache_shapes, mesh)
            cache_sds = _tree_sds(cache_shapes, cache_specs, mesh)

            def fn(params, cache, tok, pos):
                return engine.decode_step(params, cfg, cache, tok, pos)
        cache_out = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 cache_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        lowered = jax.jit(fn, out_shardings=(None, cache_out)).lower(
            p_sds, cache_sds, tok_sds, pos_sds)
        mflops = model_flops(cfg, tokens=cell.global_batch, mode="serve")

    return lowered, mesh, chips, mflops


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             out_dir: str) -> dict:
    t0 = time.time()
    lowered, mesh, chips, mflops = lower_cell(arch, shape_name, mesh_kind)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    rep = roofline.analyze(
        compiled, hlo, arch=arch, shape=shape_name, mesh=mesh_kind,
        chips=chips, model_flops=mflops)
    rec = rep.to_json()
    rec.update(
        lower_s=t_lower, compile_s=t_compile,
        temp_bytes_per_device=int(mem.temp_size_in_bytes),
        arg_bytes_per_device=int(mem.argument_size_in_bytes),
        out_bytes_per_device=int(mem.output_size_in_bytes),
        alias_bytes_per_device=int(mem.alias_size_in_bytes),
        code_bytes=int(mem.generated_code_size_in_bytes),
    )
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2, default=float)
    mem_gib = (
        rec["arg_bytes_per_device"] + rec["temp_bytes_per_device"]
    ) / 2**30
    print(f"[dryrun] {arch} {shape_name} {mesh_kind}: "
          f"compile={t_compile:.1f}s "
          f"mem/dev={mem_gib:.2f}GiB "
          f"dominant={rec['dominant']} frac={rec['roofline_fraction']:.3f}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    mmcfg.add_cli_args(ap)
    args = ap.parse_args()

    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cell_list = shapes_mod.cells(all_arch_ids(), get_config)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cell_list = [(args.arch, args.shape)]

    failures = []
    # Session-scoped matmul config: every cell lowers/compiles under one
    # mm_config layer (an AMP/chip sweep over the whole dry-run matrix is
    # a flag, not a code edit).
    with mmcfg.scope_from_args(args):
        for arch, shape in cell_list:
            for mk in meshes:
                path = os.path.join(args.out, f"{arch}__{shape}__{mk}.json")
                if args.skip_existing and os.path.exists(path):
                    continue
                try:
                    run_cell(arch, shape, mk, args.out)
                except Exception as e:  # noqa: BLE001 — report and continue
                    failures.append((arch, shape, mk, repr(e)))
                    traceback.print_exc()
                    print(f"[dryrun] FAIL {arch} {shape} {mk}: {e}",
                          file=sys.stderr)
    if failures:
        print(f"[dryrun] {len(failures)} failures", file=sys.stderr)
        sys.exit(1)
    print("[dryrun] all cells OK")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
