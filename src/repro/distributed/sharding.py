"""Logical-axis sharding rules for params, optimizer state, batches, caches.

Megatron-style TP over the "model" axis; DP over ("pod", "data"); ZeRO-1
optimizer-state sharding over "data".  Rules are name-based over parameter
tree paths (one rule table instead of a hand-maintained parallel spec tree),
with divisibility guards that fall back to replication — which is what makes
the same rules valid for full-size production configs and tiny smoke
configs alike.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh):
    """The data-parallel mesh axes (pod composes with data when present)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# Mesh used for in-model sharding annotations (set by dryrun/costprobe/
# trainer before tracing; None => constraints are no-ops, e.g. CPU tests).
_ANNOTATE_MESH: Mesh | None = None


def set_annotation_mesh(mesh: Mesh | None) -> None:
    global _ANNOTATE_MESH
    _ANNOTATE_MESH = mesh


def constrain(x, *spec_entries):
    """with_sharding_constraint guarded by the annotation mesh.

    Entries may name mesh axes ("model", "dp" for the data axes) or None;
    entries whose axes don't divide the dim fall back to None.
    """
    mesh = _ANNOTATE_MESH
    if mesh is None:
        return x
    entries = []
    for e in spec_entries:
        if e == "dp":
            e = dp_axes(mesh)
        entries.append(e)
    spec = _guard(P(*entries), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _guard(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Replace any spec entry whose mesh-axis product doesn't divide the
    corresponding dim with None (replicate that dim).

    A spec *longer* than the shape is a rule bug, not a divisibility
    problem: silently truncating it (the old `zip` behavior) would shard
    fewer dims than asked with no signal, so it raises instead.
    """
    entries = tuple(spec)
    if len(entries) > len(shape):
        raise ValueError(
            f"PartitionSpec {spec} has {len(entries)} entries for a "
            f"{len(shape)}-D shape {shape}; spec must not outrank the value")
    fixed = []
    for dim, axes in zip(shape, entries + (None,) * (len(shape) - len(entries))):
        fixed.append(axes if dim % _axis_size(mesh, axes) == 0 else None)
    return P(*fixed)


# ---------------------------------------------------------------- params
# (match-by-name, ndim) -> spec builder.  Stacked layer dims are handled by
# prepending None for every leading dim beyond the rule's arity.
_COL = {"wq", "wk", "wv", "w_gate", "w_up", "in_z", "in_x", "in_dt",
        "proj_x", "proj_gate", "wq_b", "wkv_b", "wq_a"}
_ROW = {"wo", "w_down", "out_proj", "proj_out"}
_VOCAB_ROW = {"embed"}          # (V, D): shard vocab
_VOCAB_COL = {"unembed"}        # (D, V): shard vocab
_EXPERT = {"w_gate", "w_up", "w_down"}   # under "moe": (E, ...) shard E
_SHARD_LAST_VEC = {"bq", "bk", "bv", "out_norm", "a_param"}
_BLOCKDIAG = {"w_r", "w_i"}     # (nb, bw, bw): shard nb


def param_spec(path_names: list[str], leaf, mesh: Mesh) -> P:
    name = path_names[-1]
    ndim = len(leaf.shape)

    def base(rule: P, arity: int) -> P:
        lead = (None,) * (ndim - arity)
        return _guard(P(*lead, *tuple(rule)), leaf.shape, mesh)

    if "moe" in path_names and name in _EXPERT and ndim >= 3:
        return base(P("model", None, None), 3)
    if name in _VOCAB_ROW and ndim >= 2:
        return base(P("model", None), 2)
    if name in _VOCAB_COL and ndim >= 2:
        return base(P(None, "model"), 2)
    if name in _BLOCKDIAG and ndim >= 3:
        return base(P("model", None, None), 3)
    if name in _COL and ndim >= 2:
        return base(P(None, "model"), 2)
    if name in _ROW and ndim >= 2:
        return base(P("model", None), 2)
    if name in _SHARD_LAST_VEC and ndim >= 1:
        return base(P("model"), 1)
    if name in ("conv_w", "conv_x") and ndim >= 2:
        return base(P(None, "model"), 2)
    if ndim >= 2:
        # An unrecognized >=2-D weight replicates silently — that is the
        # safe fallback, but on a real mesh it costs memory and collective
        # bandwidth, so make it visible: the obs metrics registry counts
        # every fall-through (`sharding.unmatched_params`) and provenance
        # snapshots pick it up via the guard/obs counter surface.
        from repro.obs import metrics as _metrics

        _metrics.REGISTRY.inc("sharding.unmatched_params")
    return P(*(None,) * ndim)


def tree_param_specs(shapes, mesh: Mesh, *, fsdp: bool = False):
    """Pytree of PartitionSpecs matching a pytree of arrays/SDS.

    fsdp=True additionally shards the largest still-replicated dim of every
    >=2-D weight over the data axes (ZeRO-3 / FSDP: params are all-gathered
    per layer at use; required for >60B archs to fit v5e HBM — see
    EXPERIMENTS.md §Perf iteration A2).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = []
    for path, leaf in flat:
        names = [str(getattr(k, "key", k)) for k in path]
        spec = param_spec(names, leaf, mesh)
        if fsdp and len(leaf.shape) >= 2:
            spec = zero1_spec(spec, leaf.shape, mesh)
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


# ----------------------------------------------------------- optimizer state
def zero1_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """ZeRO-1: additionally shard the largest replicated dim over "data".

    No-op when the spec already consumes the data axis (FSDP params)."""
    entries = list(tuple(spec) + (None,) * (len(shape) - len(spec)))
    for e in entries:
        axes = (e,) if isinstance(e, str) else (e or ())
        if "data" in axes:
            return P(*entries)
    dsize = _axis_size(mesh, "data")
    best, best_dim = -1, -1
    for i, (dim, axes) in enumerate(zip(shape, entries)):
        if axes is None and dim % dsize == 0 and dim > best:
            best, best_dim = dim, i
    if best_dim >= 0 and best >= dsize:
        entries[best_dim] = "data"
    return P(*entries)


def tree_optstate_specs(param_specs, shapes, mesh: Mesh):
    return jax.tree.map(
        lambda sp, sh: zero1_spec(sp, sh.shape, mesh), param_specs, shapes)


# ----------------------------------------------------------------- batches
def batch_spec(shape: tuple[int, ...], mesh: Mesh) -> P:
    """Shard dim0 (global batch) over DP axes when divisible."""
    dp = dp_axes(mesh)
    if shape[0] % _axis_size(mesh, dp) == 0:
        return P(dp, *(None,) * (len(shape) - 1))
    return P(*(None,) * len(shape))


def tree_batch_specs(batch, mesh: Mesh):
    return jax.tree.map(lambda x: batch_spec(x.shape, mesh), batch)


# ----------------------------------------------------------------- caches
def cache_leaf_spec(name: str, leaf, mesh: Mesh) -> P:
    """Cache leaves carry a leading stacked-layer dim R, then batch.

    k/v (R,B,L,KV,hd): heads over model if divisible, else L over model.
    latent/k_rope (R,B,L,r): L over model.
    state (R,B,H,S,P): H over model.  lru (R,B,W): W over model.
    conv (R,B,K-1,C): C over model.  cross k/v (R,B,F,H,hd): heads.
    """
    shape = leaf.shape
    dp = dp_axes(mesh)
    b_ax = dp if shape[1] % _axis_size(mesh, dp) == 0 else None
    msz = _axis_size(mesh, "model")
    if name in ("k", "v", "self_k", "self_v", "cross_k", "cross_v"):
        if shape[3] % msz == 0:
            return _guard(P(None, b_ax, None, "model", None), shape, mesh)
        return _guard(P(None, b_ax, "model", None, None), shape, mesh)
    if name in ("latent", "k_rope"):
        return _guard(P(None, b_ax, "model", None), shape, mesh)
    if name == "state":
        return _guard(P(None, b_ax, "model", None, None), shape, mesh)
    if name == "lru":
        return _guard(P(None, b_ax, "model"), shape, mesh)
    if name in ("conv", "cx"):
        return _guard(P(None, b_ax, None, "model"), shape, mesh)
    if name in ("cb", "cc"):
        return _guard(P(None, b_ax, None, None), shape, mesh)
    return P(*(None,) * len(shape))


def tree_cache_specs(cache, mesh: Mesh):
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    specs = []
    for path, leaf in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        specs.append(cache_leaf_spec(name, leaf, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


# ------------------------------------------------------------- assembling
def named(tree_specs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def shard_like(tree, specs, mesh: Mesh):
    """device_put a concrete pytree according to a spec pytree."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


# ------------------------------------------------- planner bridge (ShardSpec)
def matmul_shard_spec(mesh: Mesh, *, batch_axes=None, m_axes=None,
                      k_axes=None, n_axes=None, partials: str = "all_reduce",
                      zero3: bool = False):
    """Derive the planner's `costmodel.ShardSpec` from named mesh axes.

    Each kwarg names the mesh axis (or tuple of axes) a matmul dim is
    split over; the shard count is the product of those axis sizes.  This
    is how the name-based rules above talk to the cost model: e.g. a
    Megatron column-parallel GEMM on mesh (data=4, model=2) is
    ``matmul_shard_spec(mesh, batch_axes="data", n_axes="model")``.  Works
    with `AbstractMesh` too — only axis sizes are read, no devices.
    """
    from repro.core.costmodel import ShardSpec

    return ShardSpec(
        m=_axis_size(mesh, m_axes), k=_axis_size(mesh, k_axes),
        n=_axis_size(mesh, n_axes), batch=_axis_size(mesh, batch_axes),
        partials=partials, zero3=zero3)


def tp_matmul_spec(mesh: Mesh, kind: str, *, dp: bool = True):
    """The two Megatron tensor-parallel GEMM conventions as ShardSpecs.

    kind="col" — column-parallel (wq/w_up...): N over "model", activations
    gathered over the n-group.  kind="row" — row-parallel (wo/w_down...):
    K over "model", partials all-reduced.  `dp` additionally splits batch
    over the data axes when the mesh has them.
    """
    if kind not in ("col", "row"):
        raise ValueError(f"kind must be 'col' or 'row', got {kind!r}")
    batch_axes = None
    if dp:
        present = tuple(a for a in dp_axes(mesh) if a in mesh.axis_names)
        batch_axes = present or None
    if kind == "col":
        return matmul_shard_spec(mesh, batch_axes=batch_axes, n_axes="model")
    return matmul_shard_spec(mesh, batch_axes=batch_axes, k_axes="model",
                             partials="all_reduce")
