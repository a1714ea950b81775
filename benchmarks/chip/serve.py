"""Serving cells: the program's `Scheduler.step()` driven on the host's
clock by the mix's generator, then the served tokens checked against the
configuration's plain reference.

Set-up builds the weights on the device from the seed, warms every
prefill shape the mix can issue, sends the first requests (one per
client, or `max_live` for an open loop) and steps until they are all
live.  The window then runs for `--seconds` (or, where the mix gives
`window_tokens`, until that many tokens are stamped: toy rehearsals,
whose work must not depend on how fast the CPU is).  A token is stamped
with the time the `step()` that produced it returned (the step ends in a
host sync, its argmax).

A traced run also records the HLO of the compiled decode programs the
scheduler holds, for the device time per named scope
(`trace_reduce.scope_split`).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chip import flops, harness, trace_reduce, traffic
from chip.stats import percentile


class _Req:
    __slots__ = ("rid", "prompt", "out_len", "sent", "stamps", "client")

    def __init__(self, rid, prompt, out_len, sent, client):
        self.rid, self.prompt, self.out_len = rid, prompt, out_len
        self.sent, self.client = sent, client
        self.stamps: list[float] = []


def warm_shapes(params, cfg, table, prompt_buckets) -> None:
    """Run one prefill eagerly at every (batch bucket, prompt bucket) the
    mix can issue, so that the window loads and never compiles them."""
    import jax
    import jax.numpy as jnp

    from repro.serve import engine

    for b in table.batch_buckets:
        for pb in prompt_buckets:
            cache, logits = engine.prefill(
                params, cfg, jnp.zeros((b, pb), jnp.int32),
                max_len=table.max_len, last_index=jnp.zeros((b,), jnp.int32))
            jax.block_until_ready(logits)
            del cache, logits


def run(ctx, t0: float) -> dict:
    import jax

    from repro.models.model import build_model
    from repro.serve.sched import AdmissionPolicy, BucketTable, Request, Scheduler
    from repro.serve.sched.buckets import bucket_up

    mix, c, seed = ctx.mix, ctx.config, ctx.seed
    cfg = ctx.program.model_config(c, ctx.cell["config"])
    gen = traffic.ServeTraffic(mix, seed, cfg.vocab_size)
    live_cap = mix["max_live"]
    params = build_model(cfg).init(jax.random.PRNGKey(traffic.seed32(seed)))
    jax.block_until_ready(params)
    table = BucketTable.for_workload(
        max_batch=live_cap, max_prompt=gen.max_prompt,
        max_new=gen.max_output, min_prompt=mix["min_prompt_bucket"])
    if table.max_len != mix["max_len"]:
        raise ValueError(f"max_len {table.max_len} != mix {mix['max_len']}")
    buckets = [b for b in table.prompt_buckets
               if b >= bucket_up(mix["prompt"]["min"])]
    warm_shapes(params, cfg, table, buckets)
    sched = Scheduler(params, cfg, table, policy=AdmissionPolicy(
        max_live=live_cap, max_admit_per_tick=live_cap))

    reqs: dict[int, _Req] = {}
    seen: dict[int, int] = {}          # rid -> tokens stamped so far
    open_loop = mix["loop"] == "open"
    clients = live_cap if open_loop else mix["clients"]
    lateness: list[float] = []

    def send(now: float, due: float, client: int) -> None:
        """Submit the generator's next request, due at `due`."""
        rid, prompt, out_len = gen.next()
        reqs[rid] = _Req(rid, prompt, out_len, due, client)
        seen[rid] = 0
        sched.submit(Request(rid=rid, tokens=tuple(int(t) for t in prompt),
                             max_new=out_len, arrival=sched.clock.now))

    ticks: list[dict] = []
    free_clients: list[tuple[int, float]] = []
    stamped = 0

    def step(trace: bool) -> float:
        """One `Scheduler.step()`; stamps the tokens it produced and frees
        the clients whose requests completed."""
        nonlocal stamped
        admit = any(n == 0 for n in seen.values()) and sched.n_live < live_cap
        t0 = time.perf_counter()
        name = "bench.tick.admit" if admit else "bench.tick.decode"
        with harness.annotate(name, trace, step_num=len(ticks)):
            sched.step()
        t1 = time.perf_counter()
        with harness.annotate("bench.stamp", trace):
            prefill, decode_pos = [], []
            for rid in list(seen):
                toks = _tokens_of(sched, rid)
                if toks is None:
                    continue
                before, n = seen[rid], len(toks)
                r = reqs[rid]
                if n > before:
                    r.stamps.extend([t1] * (n - before))
                    stamped += n - before
                    seen[rid] = n
                    if before == 0:
                        prefill.append(len(r.prompt))
                    # a row decoded this tick wrote the K/V of its last
                    # token at this position
                    first_decoded = before if before else 1
                    decode_pos.extend(len(r.prompt) + k - 1
                                      for k in range(first_decoded, n))
                if rid in sched.results:
                    del seen[rid]
                    if r.client >= 0:
                        free_clients.append((r.client, t1))
        ticks.append({
            "t0": t0, "t1": t1, "admit": bool(prefill),
            "prompt_tokens": sum(prefill), "positions": decode_pos,
            "prefill_flops": sum(flops.prefill_flops(ctx.dims, p)
                                 for p in prefill),
            "decode_flops": flops.decode_flops(ctx.dims, decode_pos)})
        return t1

    # set-up: every client's first request, stepped until all are live
    now = time.perf_counter()
    for k in range(clients):
        send(now, now, k if not open_loop else -1)
    while any(n == 0 for n in seen.values()):
        step(False)
    ticks.clear()

    # window
    counters = ctx.compile_counter()
    trace_dir = ctx.start_trace()
    t_open = time.perf_counter()
    deadline = t_open + ctx.seconds
    next_due = t_open + (gen.gap_s(len(reqs)) if open_loop else 0.0)
    t = t_open
    stamped = 0
    target = mix.get("window_tokens")
    while stamped < target if target else t < deadline:
        with harness.annotate("bench.generator", ctx.trace):
            now = time.perf_counter()
            if open_loop:
                while next_due <= now:
                    lateness.append(now - next_due)
                    send(now, next_due, -1)
                    next_due += gen.gap_s(len(reqs))
            else:
                for k, when in free_clients:
                    due = when + mix["think_s"]
                    lateness.append(max(0.0, now - due))
                    send(now, due, k)
                free_clients.clear()
        if not seen:
            # nothing to serve until the next arrival
            with harness.annotate("bench.wait", ctx.trace):
                time.sleep(max(0.0, min(next_due, deadline)
                               - time.perf_counter()))
            t = time.perf_counter()
            continue
        t = step(ctx.trace)
    t_close = t
    trace_path = ctx.stop_trace(trace_dir)
    window_counts = counters.read()
    memory_peak = ctx.memory_peak()

    rec = _record(ctx, reqs, ticks, t_open, t_close, window_counts, lateness)
    rec["setup_s"] = t_open - t0
    rec["queued_at_close"] = sum(1 for n in seen.values() if n == 0)
    rec["notes"].append(f"requests waiting for admission at the close: "
                        f"{rec['queued_at_close']}")
    rec["attempted"] = len(reqs)
    rec["failed"] = 0
    rec["memory_peak_bytes"] = memory_peak
    if trace_path:
        rec["trace_path"] = trace_path
        rec["decode_hlo"] = [trace_reduce.hlo_ops(p.as_text())
                             for p in decode_programs(sched)]

    # every token served so far, of finished requests and of those still
    # streaming when the window closed
    results = {rid: list(sched.results[rid]["tokens"])
               for rid in sched.results}
    for lv in sched.live.values():
        results[lv.req.rid] = list(lv.generated)
    del sched, params
    gc.collect()
    widest, n, low = gaps(ctx, reqs, results, control=ctx.control)
    lim = ctx.limits
    rec["checks"] = {
        "served_tokens_checked": {"value": n, "pass_if": ">=",
                                  "limit": lim["served_tokens_checked"]},
        "widest_gap_logits": {"value": widest, "pass_if": "<=",
                              "limit": lim["widest_gap_logits"]}}
    if ctx.control:
        rec["control"] = {"program_widest_gap_logits": widest,
                          "control_widest_gap_logits": low,
                          "served_tokens_checked": n}
    return rec


def decode_programs(sched) -> list:
    """The compiled decode programs (`engine.DecodeExecutables`, one per
    slab bucket) that the scheduler holds.  The holder has no public
    accessor, so its private table is read here."""
    return [compiled for _, compiled in sched._decode._programs.values()]


def _tokens_of(sched, rid):
    if rid in sched.results:
        return sched.results[rid]["tokens"]
    for lv in sched.live.values():
        if lv.req.rid == rid:
            return lv.generated
    return None


def _record(ctx, reqs, ticks, t_open, t_close, counts, lateness) -> dict:
    window = t_close - t_open
    ttft, gaps, out_tokens = [], [], 0
    for r in reqs.values():
        st = r.stamps
        if st and t_open <= st[0] <= t_close:
            ttft.append(st[0] - r.sent)
        inside = [s for s in st if t_open <= s <= t_close]
        out_tokens += len(inside)
        gaps.extend(b - a for a, b in zip(inside, inside[1:]))
    late = sorted(lateness)
    notes = [
        f"window {window:.3f} s: {len(ticks)} ticks, {out_tokens} output "
        f"tokens, {sum(t['admit'] for t in ticks)} admitting ticks",
        f"samples: ttft {len(ttft)} requests, itl {len(gaps)} gaps",
        "itl percentiles (ms) p50/p90/p95/p99: " + "/".join(
            f"{(percentile(gaps, p) or 0.0) * 1e3:.1f}"
            for p in (50, 90, 95, 99)),
        f"compile events in the window: {counts}",
        f"generator lateness over {len(late)} sends: max "
        f"{late[-1] if late else 0.0:.4f} s, median "
        f"{late[len(late) // 2] if late else 0.0:.4f} s"]
    return {"kind": "serve", "window_s": window, "ttft_s": ttft,
            "itl_s": gaps, "out_tokens": out_tokens, "steps": ticks,
            "step_span": "bench.tick", "compile_counts": counts,
            "dims": ctx.dims, "peaks": ctx.peaks,
            "notes": notes}


# ------------------------------------------------------------ correctness
def pick_sample(reqs, results, seed: int, target_tokens: int) -> list[int]:
    """Requests to check, drawn from the seed among those that were
    served tokens (finished, or streaming when the window closed): the
    longest (prompt + served tokens) first, then others until
    `target_tokens` served tokens are in."""
    done = sorted(rid for rid in results)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(reqs[r].prompt) + len(results[r]),
                                       -r))
    rng = np.random.default_rng(traffic.seed32(seed) + 1)
    rest = [r for r in rng.permutation(done) if r != longest]
    out, n = [longest], len(results[longest])
    for r in rest:
        if n >= target_tokens:
            break
        out.append(int(r))
        n += len(results[r])
    return out


def reference_inputs(reqs, results, sample):
    """Right-padded prompt + served tokens, the positions whose next token
    was served, and those tokens; a mask of the real entries."""
    seqs = [list(reqs[r].prompt) + results[r][:-1] for r in sample]
    width = max(len(s) for s in seqs)
    n_out = max(len(results[r]) for r in sample)
    tokens = np.zeros((len(sample), width), np.int32)
    idx = np.zeros((len(sample), n_out), np.int32)
    served = np.zeros((len(sample), n_out), np.int32)
    mask = np.zeros((len(sample), n_out), bool)
    for i, r in enumerate(sample):
        tokens[i, :len(seqs[i])] = seqs[i]
        k = len(results[r])
        first = len(reqs[r].prompt) - 1
        idx[i, :k] = np.arange(first, first + k)
        served[i, :k] = results[r]
        mask[i, :k] = True
    return tokens, idx, served, mask


def gaps(ctx, reqs, results, *, control: bool = False):
    """(widest gap of a served token, served tokens compared, and with
    `control` the widest gap of the low-precision reference's choices)."""
    ref = harness.reference(ctx.config, ctx.root)
    arch = ref.Arch.from_config(ctx.config)
    sample = pick_sample(reqs, results, ctx.seed, ctx.mix["check_tokens"])
    if not sample:
        return None, 0, None
    tokens, idx, served, mask = reference_inputs(reqs, results, sample)
    g_served, g_low = ref.serve_gaps(traffic.seed32(ctx.seed), arch, tokens,
                                     idx, served, control=control)
    g_served = np.asarray(g_served)[mask]
    low = float(np.asarray(g_low)[mask].max()) if control else None
    return float(g_served.max()), int(mask.sum()), low
