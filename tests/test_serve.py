"""Serving correctness: prefill/decode must match the full forward exactly
(capacity set drop-free for MoE so the comparison is well-defined)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import all_arch_ids, get_config
from repro.models.model import build_model
from repro.serve import encdec_engine, engine, kvcache

RNG = np.random.default_rng(13)
DECODER_ARCHS = [a for a in all_arch_ids()
                 if get_config(a).family != "encdec"]


def _nodrop(cfg):
    if cfg.n_experts:
        return dataclasses.replace(cfg,
                                   capacity_factor=float(cfg.n_experts))
    return cfg


@pytest.mark.parametrize("arch", DECODER_ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = _nodrop(get_config(arch).reduced())
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(1))
    B, S, MAX = 2, 48, 80   # MAX covers S + VLM prefix + decode steps
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (B, S + 2)),
                       jnp.int32)
    batch = {"tokens": toks}
    pe = None
    if cfg.family == "vlm":
        pe = jnp.asarray(RNG.normal(size=(B, cfg.frontend_len, cfg.d_model))
                         * 0.1, jnp.float32)
        batch["prefix_embeds"] = pe
    h, _ = bundle.hidden_fn(params, batch)
    offset = cfg.frontend_len if cfg.family == "vlm" else 0

    cache, logits = engine.prefill(params, cfg, toks[:, :S], max_len=MAX,
                                   prefix_embeds=pe)
    np.testing.assert_allclose(logits, bundle.logits_fn(params, h[:, -3]),
                               rtol=2e-3, atol=2e-3)
    for i, col in enumerate((S, S + 1)):
        logits, cache = engine.decode_step(
            params, cfg, cache, toks[:, col],
            jnp.asarray(col + offset, jnp.int32))
        want = bundle.logits_fn(params, h[:, -(2 - i)])
        np.testing.assert_allclose(logits, want, rtol=2e-3, atol=2e-3)


def test_encdec_prefill_decode():
    cfg = get_config("seamless-m4t-large-v2").reduced()
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(1))
    B, S, F, MAX = 2, 24, 16, 32
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (B, S + 1)),
                       jnp.int32)
    frames = jnp.asarray(RNG.normal(size=(B, F, cfg.d_model)) * 0.1,
                         jnp.float32)
    h, _ = bundle.hidden_fn(params, {"tokens": toks, "frames": frames})
    cache, logits = encdec_engine.prefill(params, cfg, frames, toks[:, :S],
                                          max_len=MAX)
    np.testing.assert_allclose(logits, bundle.logits_fn(params, h[:, -2]),
                               rtol=2e-3, atol=2e-3)
    logits, _ = encdec_engine.decode_step(params, cfg, cache, toks[:, S],
                                          jnp.asarray(S, jnp.int32))
    np.testing.assert_allclose(logits, bundle.logits_fn(params, h[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_ring_cache_matches_full_for_local_attention():
    """Local-attention ring cache (window-sized) must equal a full cache."""
    cfg = get_config("recurrentgemma-9b").reduced()  # window 64 -> ring
    assert cfg.local_window is not None
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(2))
    B = 1
    S = cfg.local_window + 24            # prompt longer than the window
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (B, S + 1)),
                       jnp.int32)
    h, _ = bundle.hidden_fn(params, {"tokens": toks})
    cache, _ = engine.prefill(params, cfg, toks[:, :S], max_len=S + 8)
    logits, _ = engine.decode_step(params, cfg, cache, toks[:, S],
                                   jnp.asarray(S, jnp.int32))
    np.testing.assert_allclose(logits, bundle.logits_fn(params, h[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_kv_slot_positions():
    # full cache
    pos = kvcache.kv_slot_positions(jnp.asarray(5), 8, False)
    np.testing.assert_array_equal(np.asarray(pos),
                                  [0, 1, 2, 3, 4, 5, -1, -1])
    # ring cache of 4 at pos 5: slots hold 4, 5, 2, 3
    pos = kvcache.kv_slot_positions(jnp.asarray(5), 4, True)
    np.testing.assert_array_equal(np.asarray(pos), [4, 5, 2, 3])
    # ring not yet wrapped
    pos = kvcache.kv_slot_positions(jnp.asarray(1), 4, True)
    np.testing.assert_array_equal(np.asarray(pos), [0, 1, -1, -1])


def test_mla_cache_is_compressed():
    """MLA cache must be ~(kvr+rd)/(2*H*hd) of the GQA-equivalent size."""
    cfg = get_config("deepseek-v3-671b")
    cache = jax.eval_shape(lambda: kvcache.init_cache(cfg, 1, 1024))
    total = sum(np.prod(s.shape) * s.dtype.itemsize
                for s in jax.tree.leaves(cache))
    gqa_equiv = (cfg.n_layers * 1024 *
                 2 * cfg.n_heads * cfg.head_dim * 2)  # bf16 k+v
    assert total < 0.05 * gqa_equiv


# ---------------------------------------------------------------- sched
from repro.core import skewmm                      # noqa: E402
from repro.core.config import mm_config            # noqa: E402
from repro.guard import faults as gfaults          # noqa: E402
from repro.guard import health as ghealth          # noqa: E402
from repro.serve.sched import (                    # noqa: E402
    AdmissionPolicy,
    BucketTable,
    Scheduler,
    assert_covered,
    build_tuned_cache,
    capture_gemm_specs,
    min_full_batch,
    scripted_trace,
)
from repro.obs import REGISTRY, trace_scope        # noqa: E402
from repro.serve.sched.buckets import bucket_up    # noqa: E402
from repro.tune import runtime as tune_runtime     # noqa: E402
from repro.tune.cache import TuneCache             # noqa: E402


def _sched_model(arch="phi4-mini-3.8b"):
    cfg = get_config(arch).reduced()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def test_pad_axis_and_place_kv():
    t = jnp.arange(6.0).reshape(2, 3)
    padded = kvcache.pad_axis(t, 1, 5)
    assert padded.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(padded[:, :3]), np.asarray(t))
    np.testing.assert_array_equal(np.asarray(padded[:, 3:]), 0.0)
    with pytest.raises(ValueError):
        kvcache.pad_axis(t, 1, 2)  # shrink is not padding


def test_slot_free_list():
    fl = kvcache.SlotFreeList(2)
    assert fl.alloc() == 0 and fl.alloc() == 1
    with pytest.raises(IndexError):
        fl.alloc()                       # exhausted
    fl.release(0)
    with pytest.raises(ValueError):
        fl.release(0)                    # double free
    fl.grow(4)
    # lowest-first: freed 0 beats the new rows 2, 3
    assert fl.alloc() == 0 and fl.alloc() == 2
    assert fl.capacity == 4 and len(fl) == 1


def test_kv_slot_positions_batched():
    pos = kvcache.kv_slot_positions(jnp.asarray([1, 3]), 4, False)
    np.testing.assert_array_equal(np.asarray(pos),
                                  [[0, 1, -1, -1], [0, 1, 2, 3]])


def test_bucket_table():
    assert [bucket_up(d) for d in (1, 2, 3, 9, 16)] == [1, 2, 4, 16, 16]
    table = BucketTable.for_workload(max_batch=4, max_prompt=12, max_new=4)
    assert table.batch_buckets == (1, 2, 4)
    assert table.prompt_buckets == (1, 2, 4, 8, 16)
    assert table.max_len == 20
    # stability: every size in a bucket maps to that bucket
    for s in range(5, 9):
        assert table.prompt_bucket(s) == 8
    with pytest.raises(ValueError):
        table.prompt_bucket(17)
    with pytest.raises(ValueError):
        BucketTable(batch_buckets=(3,), prompt_buckets=(8,),
                    max_new=1, max_len=16)


def test_bucket_table_rejects_non_attention():
    table = BucketTable.for_workload(max_batch=2, max_prompt=8, max_new=2)
    with pytest.raises(ValueError, match="attention-only"):
        table.validate_for(get_config("mamba2-2.7b").reduced())


def test_scheduler_completes_and_respects_admission_bound():
    cfg, params = _sched_model()
    table = BucketTable.for_workload(max_batch=4, max_prompt=16, max_new=4)
    policy = AdmissionPolicy(max_live=2, max_admit_per_tick=2)
    trace = scripted_trace(
        [(0, 3, 2), (0, 9, 2), (0, 5, 2), (1, 12, 1), (3, 2, 2)],
        vocab_size=cfg.vocab_size, seed=11)
    sched = Scheduler(params, cfg, table, policy=policy, guard=False)
    for r in trace:
        sched.submit(r)
    for _ in range(50):
        if not sched.queue and not sched.live:
            break
        sched.step()
        assert sched.n_live <= policy.max_live
    assert sorted(sched.results) == [r.rid for r in trace]
    for r in trace:
        assert len(sched.results[r.rid]["tokens"]) == r.max_new
    assert sched.telemetry.completed == len(trace)


def test_join_leave_logits_bit_identical_to_solo_decode():
    """Continuous batching must not perturb survivors: every logits row,
    across joins, leaves and slab growth, equals the same request decoded
    alone at the same row of a slab of the same batch bucket, through the
    same compiled step.  (A batch-1 decode is no reference: it reduces in
    another order.)"""
    cfg, params = _sched_model()
    table = BucketTable.for_workload(max_batch=4, max_prompt=16, max_new=4)
    entries = [(0, 3, 4), (0, 5, 3), (1, 9, 4), (2, 2, 3)]
    trace = scripted_trace(entries, vocab_size=cfg.vocab_size, seed=7)
    sched = Scheduler(params, cfg, table, guard=False, trace_logits=True)
    for r in trace:
        sched.submit(r)
    rows, slab_at = {}, []     # rid -> slab row; tick -> its decode's batch
    while sched.queue or sched.live:
        sched.step()
        slab_at.append(sched.slab_batch)
        rows.update((lv.req.rid, row) for row, lv in sched.live.items())
    assert len(sched.results) == len(trace)
    assert slab_at[0] == 2 and sched.slab_batch == 4   # grew 2 -> 4 mid-run
    compiles = REGISTRY.value("serve_decode_compiles")

    for req in trace:
        pb = table.prompt_bucket(req.prompt_len)
        toks = np.zeros((1, pb), np.int32)
        toks[0, :req.prompt_len] = req.tokens
        solo, logits = engine.prefill(
            params, cfg, jnp.asarray(toks), max_len=table.max_len,
            last_index=jnp.asarray([req.prompt_len - 1]))
        want = [np.asarray(logits)[0]]
        row = rows[req.rid]
        admit = req.arrival + sched.results[req.rid]["ttft"] - 1
        slab = jax.tree.map(
            lambda x: jnp.zeros((x.shape[0], slab_at[admit], *x.shape[2:]),
                                x.dtype).at[:, [row]].set(x), solo)
        tok, pos = int(np.argmax(want[0])), req.prompt_len
        for tick in range(admit, admit + req.max_new - 1):
            b = slab_at[tick]
            slab = jax.tree.map(lambda x: kvcache.pad_axis(x, 1, b), slab)
            tokens = np.zeros(b, np.int32)
            positions = np.zeros(b, np.int32)
            tokens[row], positions[row] = tok, pos
            logits, slab = sched._decode(params, cfg, slab,
                                         jnp.asarray(tokens),
                                         jnp.asarray(positions))
            want.append(np.asarray(logits)[row])
            tok, pos = int(np.argmax(want[-1])), pos + 1
        got = sched.logit_trace[req.rid]
        assert len(got) == len(want) == req.max_new
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    assert REGISTRY.value("serve_decode_compiles") == compiles


def test_scheduler_tuned_mode_zero_misses():
    cfg, params = _sched_model()
    table = BucketTable.for_workload(max_batch=2, max_prompt=8, max_new=2)
    specs = capture_gemm_specs(params, cfg, table)
    cache = build_tuned_cache(params, cfg, table)
    assert_covered(cache, specs)
    trace = scripted_trace([(0, 3, 2), (0, 6, 2), (1, 8, 1)],
                           vocab_size=cfg.vocab_size, seed=5)
    ghealth.reset()
    with tune_runtime.use_cache(cache), mm_config(plan_mode="tuned"):
        sched = Scheduler(params, cfg, table)
        results = sched.run(trace, max_ticks=50)
    snap = ghealth.snapshot()
    ghealth.reset()
    assert len(results) == len(trace)
    assert snap.get("tuned_misses", 0) == 0
    assert snap.get("tuned_hits", 0) > 0


def test_scheduler_chaos_no_eviction():
    """Poisoned decode batches are scrubbed (PR 6 ladder), never evicted:
    every request still completes with its full token budget."""
    cfg, params = _sched_model()
    table = BucketTable.for_workload(max_batch=2, max_prompt=8, max_new=3)
    trace = scripted_trace([(0, 3, 3), (1, 6, 3)],
                           vocab_size=cfg.vocab_size, seed=9)
    ghealth.reset()
    with gfaults.fault_scope(seed=5, kinds=("nan_output", "inf_output")):
        sched = Scheduler(params, cfg, table)   # guard=True default
        results = sched.run(trace, max_ticks=50)
    snap = ghealth.snapshot()
    ghealth.reset()
    assert sorted(results) == [0, 1]
    for r in trace:
        assert len(results[r.rid]["tokens"]) == r.max_new
    assert snap.get("faults_injected", 0) > 0
    assert snap.get("faults_injected") == snap.get("faults_caught")
    assert snap.get("scrubbed_batches", 0) > 0


def test_moe_capacity_slots_full_when_batched():
    cfg = dataclasses.replace(
        get_config("dbrx-132b").reduced(),
        n_experts=4, n_experts_per_tok=2, capacity_factor=1.0)
    mfb = min_full_batch(cfg)
    assert mfb == 16
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    table = BucketTable.for_workload(max_batch=mfb, max_prompt=4,
                                     max_new=2, min_batch=mfb)
    trace = scripted_trace([(0, 4, 2)] * mfb,
                           vocab_size=cfg.vocab_size, seed=3)
    ghealth.reset()
    sched = Scheduler(params, cfg, table, guard=False)
    results = sched.run(trace, max_ticks=20)
    snap = ghealth.snapshot()
    ghealth.reset()
    assert len(results) == mfb
    assert snap["moe_slots_total"] > 0
    # snapshot() drops zero counters: absent == zero underfilled
    assert snap.get("moe_slots_underfilled", 0) == 0
    assert snap["moe_slots_filled"] == snap["moe_slots_total"]


# ------------------------------------------------------ compiled decode
def _counts():
    return (REGISTRY.value("serve_decode_compiles"),
            REGISTRY.value("serve_decode_eager"))


def test_decode_compiles_once_per_slab_bucket():
    cfg, params = _sched_model()
    table = BucketTable.for_workload(max_batch=4, max_prompt=8, max_new=4,
                                     min_batch=2)
    assert table.batch_buckets == (2, 4)
    trace = scripted_trace([(0, 3, 4), (0, 5, 3), (1, 6, 4), (2, 2, 3)],
                           vocab_size=cfg.vocab_size, seed=4)
    c0, e0 = _counts()
    sched = Scheduler(params, cfg, table)
    assert _counts() == (c0 + 2, e0)          # built ahead, one per bucket
    sizes = []
    for r in trace:
        sched.submit(r)
    while sched.queue or sched.live:
        sched.step()
        sizes.append(sched.slab_batch)
    assert sizes[0] == 2 and 4 in sizes       # the slab grew 2 -> 4
    assert len(sched.results) == len(trace)
    assert _counts() == (c0 + 2, e0)          # no tick traced anything
    snap = ghealth.snapshot()
    assert "serve_decode_compiles" not in snap
    assert "serve_decode_eager" not in snap


def _decoding_scheduler(cfg, params):
    """A scheduler whose two requests are both admitted at tick 0 and
    decode for five more ticks: later ticks admit (and plan) nothing."""
    table = BucketTable.for_workload(max_batch=2, max_prompt=8, max_new=7,
                                     min_batch=2)
    sched = Scheduler(params, cfg, table)
    for r in scripted_trace([(0, 3, 7), (0, 5, 7)],
                            vocab_size=cfg.vocab_size, seed=6):
        sched.submit(r)
    sched.step()
    assert sched.n_live == 2
    return sched


def test_decode_recompiles_under_a_new_plan_context():
    """A changed `mm_config` or tune cache traces anew; a program is
    never reused under another plan, and each is reused under its own."""
    cfg, params = _sched_model()
    sched = _decoding_scheduler(cfg, params)
    c0, e0 = _counts()
    with mm_config(plan_mode="naive"), skewmm.plan_capture() as log:
        sched.step()
    assert _counts() == (c0 + 1, e0)
    assert log                                # planned anew, in that mode
    with skewmm.plan_capture() as log:
        sched.step()                          # the first program again
    assert _counts() == (c0 + 1, e0)
    assert not log

    cache_a, cache_b = TuneCache(), TuneCache()
    with mm_config(plan_mode="tuned"):
        with tune_runtime.use_cache(cache_a):
            sched.step()
        with tune_runtime.use_cache(cache_b):
            sched.step()
        assert _counts() == (c0 + 3, e0)
        with tune_runtime.use_cache(cache_a):
            sched.step()
    assert _counts() == (c0 + 3, e0)
    assert sorted(sched.results) == [0, 1]    # the fifth step decoded


def test_compiled_decode_matches_eager_decode_step():
    cfg, params = _sched_model()
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    cache, logits = engine.prefill(params, cfg, toks, max_len=12)
    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    pos = jnp.asarray([8, 8], jnp.int32)
    c0, _ = _counts()
    got_logits, got_cache = engine.DecodeExecutables()(
        params, cfg, cache, tokens, pos)
    assert _counts()[0] == c0 + 1
    want_logits, want_cache = engine.decode_step(params, cfg, cache, tokens,
                                                 pos)
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(want_logits), rtol=1e-5, atol=1e-5)
    for g, w in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("scope, eager", [
    ("fault_scope", True),
    ("trace_scope", True),
    ("profiler_sink", False),
])
def test_decode_runs_eagerly_only_under_a_per_call_context(scope, eager):
    """A fault scope or a tree-building trace scope needs the step traced
    on every call; the profiler sink builds no tree and stays compiled."""
    cfg, params = _sched_model()
    sched = _decoding_scheduler(cfg, params)
    ctx = {
        "fault_scope": lambda: gfaults.fault_scope(kinds=("nan_output",),
                                                   rate=0.0),
        "trace_scope": trace_scope,
        "profiler_sink": lambda: trace_scope(profiler=True),
    }[scope]
    c0, e0 = _counts()
    steps = sched.telemetry.decode_steps
    with ctx():
        while sched.live:
            sched.step()
    ran = sched.telemetry.decode_steps - steps
    assert ran == 5
    assert _counts() == (c0, e0 + ran if eager else e0)

