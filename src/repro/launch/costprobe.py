import os
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Roofline-term extraction via composed probe lowerings.

WHY: XLA's cost_analysis() counts a while-loop body ONCE, not multiplied by
its trip count, so a monolithic lowering of a scanned 46-layer model
under-reports FLOPs by ~100x (verified: useful_ratio 124 on gemma2
train_4k).  The dry-run (launch.dryrun) therefore only proves
compile-success + memory; the roofline terms come from THIS module:

  For each (arch x shape x mesh) we lower and compile small PROBE programs
  that contain no multi-trip loops:
    * fixed — embed + final-norm + chunkless loss (+ MTP) fwd+bwd
    * one probe per distinct block kind — fwd+bwd of one block, with
      single-trip attention chunks; grads land in ZeRO-1 sharding so the
      gradient reduce-scatter collective is captured per microbatch
    * opt — the optimizer update + ZeRO-1 param all-gather
  and compose:  total = n_micro * (fixed + sum_k n_k * block_k) + opt.
  SSM blocks are probed at one SSD chunk and scaled linearly in S (the SSD
  algorithm is exactly linear in chunk count, projections linear in S).

  Every number is read from compiled.cost_analysis() / HLO text of a
  compiled artifact on the production mesh, so per-device sharding effects
  (including all inserted collectives) are real, not modeled.
"""

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import all_arch_ids, get_config
from repro.core import config as mmcfg
from repro.core import roofline
from repro.core.hw import peak_flops
from repro.distributed import sharding as shd
from repro.launch import shapes as shapes_mod
from repro.launch.mesh import make_production_mesh
from repro.models import blocks, encdec, transformer
from repro.models import layers as layers_mod
from repro.models.layers import rmsnorm

# Force single-trip attention chunking in all probes (see module docstring).
layers_mod.CHUNK_OVERRIDE = (1 << 30, 1 << 30)
from repro.models.model import model_flops, param_shapes
from repro.optim.adamw import AdamW
from repro.serve import engine, kvcache
from repro.train.loss import chunked_softmax_xent

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "roofline")


@dataclasses.dataclass
class ProbeCost:
    flops: float
    bytes: float
    coll_bytes: float
    coll_counts: dict

    def __mul__(self, k: float):
        return ProbeCost(self.flops * k, self.bytes * k,
                         self.coll_bytes * k,
                         {n: c * k for n, c in self.coll_counts.items()})

    __rmul__ = __mul__

    def __add__(self, o: "ProbeCost"):
        counts = dict(self.coll_counts)
        for n, c in o.coll_counts.items():
            counts[n] = counts.get(n, 0) + c
        return ProbeCost(self.flops + o.flops, self.bytes + o.bytes,
                         self.coll_bytes + o.coll_bytes, counts)


ZERO = ProbeCost(0.0, 0.0, 0.0, {})


def _measure(fn, *sds_args, out_shardings=None) -> ProbeCost:
    lowered = jax.jit(fn, out_shardings=out_shardings).lower(*sds_args)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    cs = roofline.collective_stats(compiled.as_text())
    return ProbeCost(float(ca.get("flops", 0.0)),
                     float(ca.get("bytes accessed", 0.0)),
                     cs.total_bytes, cs.counts)


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _stack1(tree):
    """Add a leading stacked-layer dim of 1 (to reuse stage param specs)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((1,) + tuple(s.shape), s.dtype), tree)


class CellProber:
    def __init__(self, arch: str, shape_name: str, mesh_kind: str):
        self.arch = arch
        self.cfg = get_config(arch)
        self.cell = shapes_mod.SHAPES[shape_name]
        self.mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
        shd.set_annotation_mesh(self.mesh)
        self.chips = int(np.prod(list(self.mesh.shape.values())))
        self.mesh_kind = mesh_kind
        self.n_micro = shapes_mod.microbatches_for(self.cfg, self.cell)
        self.dtype = jnp.dtype(self.cfg.dtype)
        self.dp = shd.dp_axes(self.mesh)
        from repro.launch.dryrun import _use_fsdp
        self.fsdp = _use_fsdp(self.cfg)

    # -------------------------------------------------------------- utils
    def _x_sds(self, b, s):
        spec = shd.batch_spec((b, s, self.cfg.d_model), self.mesh)
        return _sds((b, s, self.cfg.d_model), self.dtype, self.mesh, spec)

    # ---------------------------------------------- attention traffic fix
    # The jnp blockwise-attention path materializes the (B,H,S,S) score
    # chain, which XLA's byte accounting charges to HBM; the production
    # TPU path is the Pallas flash kernel (kernels/flash_attention.py),
    # whose HBM traffic is fully determined by its BlockSpec: per (b, h,
    # q-block): q read once, k/v streamed once per q-block, o written once
    # (scores never leave VMEM).  We therefore probe the jnp attention
    # chain in isolation (same shapes/shardings) and replace its bytes
    # with the BlockSpec-derived kernel traffic.  FLOPs are identical and
    # stay measured.  bq=2048/bkv=1024 fit comfortably in the AMP-budgeted
    # VMEM (planner-checked) and give gq = S/2048 k/v revisits.
    _FLASH_BQ = 2048

    def _attn_dims(self, kind: str):
        cfg = self.cfg
        if cfg.use_mla:
            return (cfg.n_heads, cfg.n_heads, cfg.qk_nope_dim +
                    cfg.qk_rope_dim, cfg.v_head_dim)
        return cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.head_dim

    def _flash_traffic_bytes(self, kind: str, b: int, s: int) -> float:
        """Per-DEVICE flash-kernel HBM bytes for one layer, fwd pass."""
        cfg = self.cfg
        hq, hkv, dq, dv = self._attn_dims(kind)
        window = cfg.local_window if kind == "attn_local" else None
        msz = self.mesh.shape["model"]
        dsz = 1
        for a in self.dp:
            dsz *= self.mesh.shape[a]
        b_l = max(b // dsz, 1)
        hq_l = max(hq // msz, 1)
        # kv heads replicate when < msz (grouped via BlockSpec index map)
        hkv_l = max(hkv // msz, 1)
        gq = max(s // self._FLASH_BQ, 1)
        kv_span = min(s, (window or s) + self._FLASH_BQ)
        q_bytes = b_l * hq_l * s * dq * 2
        o_bytes = b_l * hq_l * s * dv * 2
        kv_bytes = b_l * hkv_l * gq * kv_span * (dq + dv) * 2
        return float(q_bytes + o_bytes + kv_bytes)

    def _attn_correction(self, kind: str, b: int, s: int, *,
                         train: bool) -> ProbeCost:
        """(jnp-attention bytes -> flash-kernel bytes) delta for one layer.

        Backward factor 3.5x fwd traffic (flash bwd: re-stream k/v, read
        o/do, write dq/dk/dv — standard flash-attention-2 accounting)."""
        if s <= 1:
            return ZERO
        cfg = self.cfg
        hq, hkv, dq, dv = self._attn_dims(kind)
        window = cfg.local_window if kind == "attn_local" else None
        dp_spec = shd.batch_spec((b,), self.mesh)[0] if b > 1 else None
        hspec = "model" if hq % self.mesh.shape["model"] == 0 else None
        kvspec = "model" if hkv % self.mesh.shape["model"] == 0 else None
        q_sds = _sds((b, hq, s, dq), self.dtype, self.mesh,
                     P(dp_spec, hspec, None, None))
        k_sds = _sds((b, hkv, s, dq), self.dtype, self.mesh,
                     P(dp_spec, kvspec, None, None))
        v_sds = _sds((b, hkv, s, dv), self.dtype, self.mesh,
                     P(dp_spec, kvspec, None, None))

        def fwd(q, k, v):
            return layers_mod.blockwise_attention(
                q, k, v, causal=True, window=window,
                softcap=cfg.attn_softcap)

        if train:
            def f(q, k, v):
                return jnp.sum(fwd(q, k, v).astype(jnp.float32))
            jnp_cost = _measure(lambda q, k, v: jax.value_and_grad(
                f, argnums=(0, 1, 2))(q, k, v), q_sds, k_sds, v_sds)
            flash = 3.5 * self._flash_traffic_bytes(kind, b, s)
        else:
            jnp_cost = _measure(fwd, q_sds, k_sds, v_sds)
            flash = self._flash_traffic_bytes(kind, b, s)
        return ProbeCost(0.0, flash - jnp_cost.bytes, 0.0, {})

    def _block_params_sds(self, kind: str):
        shapes = jax.eval_shape(
            lambda k: blocks.init_block(k, self.cfg, kind),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        specs = shd.tree_param_specs(shapes, self.mesh, fsdp=self.fsdp)
        sds = jax.tree.map(
            lambda s, sp: _sds(s.shape, s.dtype, self.mesh, sp),
            shapes, specs)
        return sds, specs

    def _kind_counts(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for unit, n in self.cfg.stage_list():
            for kind in unit:
                counts[kind] = counts.get(kind, 0) + n
        return counts

    # ------------------------------------------------------------- train
    def probe_train(self) -> ProbeCost:
        cell = self.cell
        b_micro = cell.global_batch // self.n_micro
        s = cell.seq_len
        total = ZERO

        # --- per-kind block probes (fwd+bwd, grads in ZeRO-1 sharding)
        for kind, count in self._kind_counts().items():
            cost = self._probe_block_train(kind, b_micro, s)
            total = total + (count * self.n_micro) * cost

        # --- fixed: embed + final norm + loss (+ MTP) fwd+bwd
        fixed = self._probe_fixed_train(b_micro, s)
        total = total + self.n_micro * fixed

        # --- optimizer update + ZeRO-1 all-gather
        total = total + self._probe_opt()
        return total

    def _probe_block_train(self, kind: str, b, s) -> ProbeCost:
        cfg = self.cfg
        p_sds, p_specs = self._block_params_sds(kind)
        x_sds = self._x_sds(b, s)
        positions = jnp.arange(s, dtype=jnp.int32)
        # SSM blocks: probe one SSD chunk and scale linearly.
        scale = 1.0
        if kind == "ssm" and s > cfg.ssm_chunk:
            scale = s / cfg.ssm_chunk
            s_probe = cfg.ssm_chunk
            x_sds = self._x_sds(b, s_probe)
            positions = jnp.arange(s_probe, dtype=jnp.int32)
            s = s_probe

        def f(p, x):
            out, aux = blocks.block_fwd(x, p, cfg, kind, positions)
            return jnp.sum(out.astype(jnp.float32)) + aux

        grad_specs = shd.tree_optstate_specs(p_specs, p_sds, self.mesh)
        out_sh = (None, jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), grad_specs,
            is_leaf=lambda v: isinstance(v, P)))
        cost = _measure(
            lambda p, x: jax.value_and_grad(f)(p, x),
            p_sds, x_sds, out_shardings=out_sh)
        if kind.startswith("attn"):
            cost = cost + self._attn_correction(kind, b, s, train=True)
        return cost * scale

    def _probe_fixed_train(self, b, s) -> ProbeCost:
        cfg = self.cfg
        tok_spec = shd.batch_spec((b, s), self.mesh)
        tok_sds = _sds((b, s), jnp.int32, self.mesh, tok_spec)
        fixed_shapes = self._fixed_param_shapes()
        fixed_specs = shd.tree_param_specs(fixed_shapes, self.mesh,
                                           fsdp=self.fsdp)
        fixed_sds = jax.tree.map(
            lambda sh, sp: _sds(sh.shape, sh.dtype, self.mesh, sp),
            fixed_shapes, fixed_specs)

        def f(p, tokens):
            x = transformer.embed_tokens(p, cfg, tokens)
            h = rmsnorm(x, p["final_norm"], cfg.norm_eps)
            loss = chunked_softmax_xent(
                h[:, :-1], tokens[:, 1:],
                lambda hh: transformer.unembed(p, cfg, hh),
                chunk=s)                       # single trip
            if cfg.mtp_heads:
                mtp_h = transformer.mtp_hidden(p, cfg, h, tokens)
                loss = loss + 0.3 * chunked_softmax_xent(
                    mtp_h[:, :-1], tokens[:, 2:],
                    lambda hh: transformer.unembed(p, cfg, hh), chunk=s)
            return loss

        grad_specs = shd.tree_optstate_specs(fixed_specs, fixed_sds,
                                             self.mesh)
        out_sh = (None, jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), grad_specs,
            is_leaf=lambda v: isinstance(v, P)))
        return _measure(lambda p, t: jax.value_and_grad(f)(p, t),
                        fixed_sds, tok_sds, out_shardings=out_sh)

    def _fixed_param_shapes(self):
        cfg = self.cfg
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)

        def init(k):
            p = {"embed": jnp.zeros((cfg.vocab_size, cfg.d_model),
                                    self.dtype),
                 "final_norm": jnp.zeros((cfg.d_model,), self.dtype)}
            if not cfg.tie_embeddings:
                p["unembed"] = jnp.zeros((cfg.d_model, cfg.vocab_size),
                                         self.dtype)
            if cfg.mtp_heads:
                p["mtp"] = {
                    "proj": jnp.zeros((2 * cfg.d_model, cfg.d_model),
                                      self.dtype),
                    "norm": jnp.zeros((cfg.d_model,), self.dtype),
                    "block": blocks.init_block(
                        jax.random.PRNGKey(0), cfg, "attn_dense"),
                }
            return p

        return jax.eval_shape(lambda k: init(k), key)

    def _probe_opt(self) -> ProbeCost:
        shapes = param_shapes(self.cfg)
        p_specs = shd.tree_param_specs(shapes, self.mesh, fsdp=self.fsdp)
        p_sds = jax.tree.map(
            lambda s, sp: _sds(s.shape, s.dtype, self.mesh, sp),
            shapes, p_specs)
        opt = AdamW(lr=3e-4)
        opt_shapes = jax.eval_shape(opt.init, p_sds)
        mu_specs = shd.tree_optstate_specs(p_specs, opt_shapes.mu, self.mesh)
        opt_sds = type(opt_shapes)(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            mu=jax.tree.map(lambda s, sp: _sds(s.shape, s.dtype, self.mesh,
                                               sp), opt_shapes.mu, mu_specs),
            nu=jax.tree.map(lambda s, sp: _sds(s.shape, s.dtype, self.mesh,
                                               sp), opt_shapes.nu, mu_specs))
        g_sds = jax.tree.map(
            lambda s, sp: _sds(s.shape, jnp.float32, self.mesh, sp),
            shapes, p_specs)
        out_sh = (
            jax.tree.map(lambda sp: NamedSharding(self.mesh, sp), p_specs,
                         is_leaf=lambda v: isinstance(v, P)),
            type(opt_shapes)(
                step=NamedSharding(self.mesh, P()),
                mu=jax.tree.map(lambda sp: NamedSharding(self.mesh, sp),
                                mu_specs,
                                is_leaf=lambda v: isinstance(v, P)),
                nu=jax.tree.map(lambda sp: NamedSharding(self.mesh, sp),
                                mu_specs,
                                is_leaf=lambda v: isinstance(v, P))),
            None)
        return _measure(lambda g, st, p: opt.update(g, st, p),
                        g_sds, opt_sds, p_sds, out_shardings=out_sh)

    # ----------------------------------------------------------- prefill
    def probe_prefill(self) -> ProbeCost:
        cfg = self.cfg
        b, s = self.cell.global_batch, self.cell.seq_len
        total = ZERO
        for kind, count in self._kind_counts().items():
            total = total + count * self._probe_block_serve(
                kind, b, s, mode="prefill")
        total = total + self._probe_fixed_serve(b, s, decode=False)
        if cfg.family == "encdec":
            # encoder blocks over the frame sequence + decoder cross-attn
            f = min(cfg.frontend_len, s)
            total = total + cfg.enc_layers * self._probe_block_serve(
                "attn_global", b, f, mode="prefill")
            total = total + cfg.n_layers * self._probe_cross_attn(b, s, f)
        if cfg.family == "vlm":
            # prefix patch embeddings add frontend_len/s extra positions
            # through every block: scale linearly (<1% for prefill_32k).
            total = total * (1.0 + cfg.frontend_len / s)
        return total

    def _probe_cross_attn(self, b, s_q, s_kv) -> ProbeCost:
        cfg = self.cfg
        shapes = jax.eval_shape(
            lambda k: encdec.init_cross_attn(k, cfg),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        specs = shd.tree_param_specs(shapes, self.mesh)
        p_sds = jax.tree.map(
            lambda sh, sp: _sds(sh.shape, sh.dtype, self.mesh, sp),
            shapes, specs)
        x_sds = self._x_sds(b, s_q)
        e_sds = self._x_sds(b, s_kv)

        def f(p, x, enc_out):
            kv = encdec.cross_kv(enc_out, p, cfg)
            return encdec.cross_attn(x, kv, p, cfg)
        return _measure(f, p_sds, x_sds, e_sds)

    # ------------------------------------------------------------ decode
    def probe_decode(self) -> ProbeCost:
        cfg = self.cfg
        b, s = self.cell.global_batch, self.cell.seq_len
        total = ZERO
        for kind, count in self._kind_counts().items():
            total = total + count * self._probe_block_serve(
                kind, b, s, mode="decode")
        total = total + self._probe_fixed_serve(b, s, decode=True)
        if cfg.family == "encdec":
            f = min(cfg.frontend_len, s)
            total = total + cfg.n_layers * self._probe_cross_attn(b, 1, f)
        return total

    def _probe_block_serve(self, kind, b, s, *, mode) -> ProbeCost:
        cfg = self.cfg
        p_sds, _ = self._block_params_sds(kind)
        # strip the stacked dim by probing with R=1 params then slicing? —
        # block params here are unstacked already (init_block directly).
        positions = jnp.arange(s, dtype=jnp.int32)
        if mode == "prefill":
            scale = 1.0
            if kind == "ssm" and s > cfg.ssm_chunk:
                scale = s / cfg.ssm_chunk
                s = cfg.ssm_chunk
                positions = jnp.arange(s, dtype=jnp.int32)
            x_sds = self._x_sds(b, s)

            def f(p, x):
                out, e = engine._block_prefill(x, p, cfg, kind, positions, s)
                return out, e
            cost = _measure(f, p_sds, x_sds)
            if kind.startswith("attn"):
                cost = cost + self._attn_correction(kind, b, s, train=False)
            return scale * cost

        # decode: one token against the cell-sized cache
        cache_shapes = jax.eval_shape(
            lambda: kvcache.init_block_cache(cfg, kind, b, s, 1, self.dtype))
        cache_shapes = jax.tree.map(
            lambda sh: jax.ShapeDtypeStruct(sh.shape[1:], sh.dtype),
            cache_shapes)                      # drop stacked dim R=1
        cache_specs = shd.tree_cache_specs(
            jax.tree.map(lambda sh: jax.ShapeDtypeStruct(
                (1,) + tuple(sh.shape), sh.dtype), cache_shapes), self.mesh)
        cache_specs = jax.tree.map(lambda sp: P(*tuple(sp)[1:]), cache_specs,
                                   is_leaf=lambda v: isinstance(v, P))
        cache_sds = jax.tree.map(
            lambda sh, sp: _sds(sh.shape, sh.dtype, self.mesh, sp),
            cache_shapes, cache_specs)
        x_sds = self._x_sds(b, 1)
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

        def f(p, x, entry, pos):
            return engine._block_decode(x, p, cfg, kind, entry, pos)
        return _measure(f, p_sds, x_sds, cache_sds, pos_sds)

    def _probe_fixed_serve(self, b, s, *, decode: bool) -> ProbeCost:
        cfg = self.cfg
        fixed_shapes = self._fixed_param_shapes()
        fixed_specs = shd.tree_param_specs(fixed_shapes, self.mesh,
                                           fsdp=self.fsdp)
        fixed_sds = jax.tree.map(
            lambda sh, sp: _sds(sh.shape, sh.dtype, self.mesh, sp),
            fixed_shapes, fixed_specs)
        n_tok = 1 if decode else s
        tok_spec = shd.batch_spec((b, n_tok), self.mesh)
        tok_sds = _sds((b, n_tok), jnp.int32, self.mesh, tok_spec)

        def f(p, tokens):
            x = transformer.embed_tokens(p, cfg, tokens)
            h = rmsnorm(x, p["final_norm"], cfg.norm_eps)
            return transformer.unembed(p, cfg, h[:, -1])
        return _measure(f, fixed_sds, tok_sds)

    # ------------------------------------------------------------- entry
    def run(self) -> dict:
        mode = self.cell.mode
        t0 = time.time()
        if mode == "train":
            cost = self.probe_train()
            tokens = self.cell.global_batch * self.cell.seq_len
            mflops = model_flops(self.cfg, tokens=tokens, mode="train")
        elif mode == "prefill":
            cost = self.probe_prefill()
            tokens = self.cell.global_batch * self.cell.seq_len
            mflops = model_flops(self.cfg, tokens=tokens, mode="serve")
        else:
            cost = self.probe_decode()
            mflops = model_flops(self.cfg, tokens=self.cell.global_batch,
                                 mode="serve")
        # Roofline terms against the context-resolved chip (mm_config /
        # --chip), so cross-device probes report per-chip fractions.
        chip = mmcfg.current().chip_spec
        peak = peak_flops(chip, 2)
        rep = roofline.RooflineReport(
            arch=self.arch, shape=self.cell.name, mesh=self.mesh_kind,
            chips=self.chips,
            hlo_flops=cost.flops, hlo_bytes=cost.bytes,
            collective_bytes=cost.coll_bytes,
            compute_s=cost.flops / peak,
            memory_s=cost.bytes / chip.hbm_bw,
            collective_s=cost.coll_bytes / (chip.ici_bw_per_link
                                            * chip.ici_links),
            model_flops=mflops, peak_flops=peak,
            bytes_per_device=0, collective_counts=cost.coll_counts)
        rec = rep.to_json()
        rec["probe_s"] = time.time() - t0
        return rec


def _bench_record(rec: dict):
    """One probe cell as a structured BenchResult (repro.bench).

    The roofline probe emits through the same record path as the
    benchmark harness so costprobe runs join the tracked perf series:
    the deterministic roofline terms land in `metrics`, the wall time of
    the probe itself rides along informationally (it is compile time,
    not device time).
    """
    from repro.bench.record import BenchResult, Provenance

    name = f"roofline_{rec['arch']}_{rec['shape']}_{rec['mesh']}"
    # hlo_/collective_-prefixed names (and useful_ratio) are informational
    # by policy in repro.bench.compare: they come from XLA's cost_analysis,
    # which moves with jax versions, unlike the cost-model metrics.
    metrics = {
        "hlo_roofline_frac": rec["roofline_fraction"],
        "useful_ratio": rec["useful_ratio"],
        "hlo_tflops": rec["hlo_flops"] / 1e12,
        "hlo_gib": rec["hlo_bytes"] / 2**30,
        "collective_gib": rec["collective_bytes"] / 2**30,
    }
    return BenchResult(
        name=name, suite="roofline",
        axes={"arch": rec["arch"], "shape": rec["shape"],
              "mesh": rec["mesh"], "chips": rec["chips"]},
        metrics=metrics,
        info={"dominant": rec["dominant"]},
        provenance=Provenance.capture(),
        us_per_call=rec["probe_s"] * 1e6, us_iqr=None, repeats=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--bench-json", default=None,
                    help="also write the probed cells as structured "
                         "BenchResult records (repro.bench schema)")
    mmcfg.add_cli_args(ap)
    args = ap.parse_args()

    cells = (shapes_mod.cells(all_arch_ids(), get_config) if args.all
             else [(args.arch, args.shape)])
    os.makedirs(args.out, exist_ok=True)
    import traceback
    failures = []
    bench_records = []
    with mmcfg.scope_from_args(args):
        for arch, shape in cells:
            path = os.path.join(args.out,
                                f"{arch}__{shape}__{args.mesh}.json")
            if args.skip_existing and os.path.exists(path):
                continue
            try:
                rec = CellProber(arch, shape, args.mesh).run()
                with open(path, "w") as fh:
                    json.dump(rec, fh, indent=2, default=float)
                if args.bench_json:
                    bench_records.append(_bench_record(rec))
                print(f"[probe] {arch} {shape} {args.mesh}: "
                      f"dom={rec['dominant']} "
                      f"frac={rec['roofline_fraction']:.3f} "
                      f"useful={rec['useful_ratio']:.2f} "
                      f"({rec['probe_s']:.0f}s)")
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                failures.append((arch, shape, repr(e)))
    if args.bench_json:
        # Written even when empty (all cells skipped/failed) so the
        # requested output always exists and says what happened.
        from repro.bench import io as bench_io
        for p in bench_io.write_run(args.bench_json, bench_records, "full"):
            print(f"[probe] wrote {p} ({len(bench_records)} records)")
    if failures:
        print(f"[probe] {len(failures)} failures: {failures}")
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
