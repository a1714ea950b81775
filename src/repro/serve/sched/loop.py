"""The continuous-batching step loop.

One `Scheduler.step()` is one simulated tick:

1. **Admission** — pop arrived requests (FIFO, bounded by the admission
   policy and free KV rows), group them by prompt bucket, and prefill
   each group as one right-padded batch on a (batch bucket, prompt
   bucket) shape.  Prefilled rows scatter into the live KV slab at
   free-list slots; the prefill logits yield each request's first token.
2. **Batched decode** — every live request advances one token through a
   single `decode_step` at the slab's batch bucket with *per-row*
   positions.  Joins scatter in, leaves release their slot; survivors
   are never re-padded or moved (their logits stay bit-identical to a
   solo decode — tested).  The slab only grows, by zero-padding the
   batch axis to the next bucket (`kvcache.pad_axis`).

Decode runs through `guarded_decode_step`, so the guard ladder is never
bypassed: a poisoned batch is scrubbed on the XLA reference backend and
healthy requests keep their rows (chaos-tested).  MoE models batch every
live request's expert GEMMs in the same capacity slots simply by
decoding jointly; with `track_capacity_slots` armed the health ledger
proves the slots ship full.

The decode model step is compiled (`engine.DecodeExecutables`): one
program per slab batch bucket, built ahead when the scheduler is made
for every bucket up to `max_live`, so a tick traces, lowers and loads
nothing.  The guard scrub's host read stays outside it.  Where a
per-call host fact is the point (a fault scope or tripped ladder, a
tree-building trace scope, MoE slot tracking) the step runs eagerly, as
prefill always does.

Spans (`repro.obs`, one integer check each when disarmed): a `tick`
holds `admit` (with `prefill` per group) and `decode`, each model call
(eager: trace, lower, load, enqueue; compiled: enqueue); `sync` around
every device-to-host read; `scatter` around slab growth and the scatter
of prefilled rows; `bookkeep` around the per-row token loop, completions
and telemetry.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import moe
from repro.obs import spans as _obs
from repro.serve import engine, kvcache
from repro.serve.sched import moebatch
from repro.serve.sched.buckets import BucketTable
from repro.serve.sched.queue import AdmissionPolicy, Clock, Request, RequestQueue
from repro.serve.sched.telemetry import ServeTelemetry


@dataclasses.dataclass
class _Live:
    """Mutable per-slot progress of one admitted request."""

    req: Request
    row: int
    generated: list[int]
    admit_tick: int


class Scheduler:
    """Continuous-batching scheduler over a bucket table.

    `guard=True` routes decode through `guarded_decode_step` (the
    serving-boundary NaN scrub); `track_moe_slots` (default: on for MoE
    configs) arms `moe.track_capacity_slots()` around every model call.
    """

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        table: BucketTable,
        *,
        policy: AdmissionPolicy | None = None,
        clock: Clock | None = None,
        telemetry: ServeTelemetry | None = None,
        guard: bool = True,
        track_moe_slots: bool | None = None,
        trace_logits: bool = False,
    ):
        table.validate_for(cfg)
        self.params = params
        self.cfg = cfg
        self.table = table
        self.policy = policy or AdmissionPolicy(max_live=table.batch_buckets[-1])
        if self.policy.max_live > table.batch_buckets[-1]:
            raise ValueError(
                f"max_live {self.policy.max_live} exceeds the largest "
                f"batch bucket {table.batch_buckets[-1]}"
            )
        self.clock = clock or Clock()
        self.telemetry = telemetry or ServeTelemetry()
        self.guard = guard
        self.track_moe = (
            moebatch.has_moe(cfg) if track_moe_slots is None else track_moe_slots
        )
        self.queue = RequestQueue()
        self.live: dict[int, _Live] = {}
        self.results: dict[int, dict] = {}
        # rid -> [np logits row per generated token]; the join/leave
        # invariant tests compare these bit-exactly to a solo decode.
        self.trace_logits = trace_logits
        self.logit_trace: dict[int, list[np.ndarray]] = {}
        self._slab = None  # KV cache pytree at the current batch bucket
        self._free: kvcache.SlotFreeList | None = None
        self._tokens: np.ndarray | None = None  # (B,) last token per row
        self._pos: np.ndarray | None = None  # (B,) next write position
        # the decode step, compiled ahead for every slab size it can grow to
        self._decode = engine.DecodeExecutables()
        top = table.batch_bucket(self.policy.max_live)
        for b in table.batch_buckets:
            if b <= top:
                self._model_call(lambda b=b: self._decode.warm(
                    params, cfg, b, table.max_len))

    # ------------------------------------------------------------- intake
    @property
    def n_live(self) -> int:
        return len(self.live)

    @property
    def slab_batch(self) -> int:
        return 0 if self._free is None else self._free.capacity

    def submit(self, req: Request) -> None:
        self.table.prompt_bucket(req.prompt_len)  # raises if unservable
        if req.max_new > self.table.max_new:
            raise ValueError(
                f"request {req.rid}: max_new {req.max_new} exceeds table "
                f"budget {self.table.max_new}"
            )
        self.queue.push(req)

    # -------------------------------------------------------------- slab
    def _ensure_slab(self, required: int) -> None:
        cur = self.slab_batch
        if required <= cur:
            return
        new_b = self.table.batch_bucket(required)
        with _obs.span("scatter"):
            self._grow_slab(cur, new_b)

    def _grow_slab(self, cur: int, new_b: int) -> None:
        if self._slab is None:
            self._slab = kvcache.init_cache(self.cfg, new_b, self.table.max_len)
            self._free = kvcache.SlotFreeList(new_b)
            self._tokens = np.zeros(new_b, np.int32)
            self._pos = np.zeros(new_b, np.int32)
        else:
            # grow only: survivors keep their rows (bit-identical logits)
            self._slab = jax.tree.map(
                lambda x: kvcache.pad_axis(x, 1, new_b), self._slab
            )
            self._free.grow(new_b)
            self._tokens = np.pad(self._tokens, (0, new_b - cur))
            self._pos = np.pad(self._pos, (0, new_b - cur))

    def _model_call(self, thunk):
        if self.track_moe:
            with moe.track_capacity_slots():
                return thunk()
        return thunk()

    # --------------------------------------------------------- admission
    def _prefill_group(self, reqs: list[Request], pb: int, now: int) -> None:
        n = len(reqs)
        b_pad = self.table.batch_bucket(n)
        with _obs.span("prefill", f"pb{pb}", bucket=pb, n=n, batch=b_pad):
            self._prefill_group_inner(reqs, pb, now, n, b_pad)

    def _prefill_group_inner(self, reqs: list[Request], pb: int, now: int,
                             n: int, b_pad: int) -> None:
        tokens = np.zeros((b_pad, pb), np.int32)
        last = np.zeros(b_pad, np.int32)
        for i, r in enumerate(reqs):
            tokens[i, : r.prompt_len] = r.tokens
            last[i] = r.prompt_len - 1
        cache, logits = self._model_call(
            lambda: engine.prefill(
                self.params,
                self.cfg,
                jnp.asarray(tokens),
                max_len=self.table.max_len,
                last_index=jnp.asarray(last),
            )
        )
        with _obs.span("sync"):
            first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            if self.trace_logits:
                rows_np = np.asarray(logits)
                for i, r in enumerate(reqs):
                    self.logit_trace[r.rid] = [rows_np[i]]
        rows = np.asarray([self._free.alloc() for _ in reqs], np.int32)
        # pad-on-device stays on device: scatter the n real rows into the
        # slab at their allocated slots (unpad-on-fetch).
        with _obs.span("scatter"):
            self._slab = jax.tree.map(
                lambda slab, new: slab.at[:, rows].set(new[:, :n]),
                self._slab,
                cache,
            )
        with _obs.span("bookkeep"):
            self._admit_rows(reqs, rows, first, now)

    def _admit_rows(self, reqs: list[Request], rows: np.ndarray,
                    first: np.ndarray, now: int) -> None:
        self.telemetry.prefill_batches += 1
        for i, r in enumerate(reqs):
            row = int(rows[i])
            lv = _Live(req=r, row=row, generated=[int(first[i])], admit_tick=now)
            self.telemetry.observe_admission(now - r.arrival)
            self.telemetry.observe_first_token(now - r.arrival + 1)
            self.telemetry.tokens_out += 1
            if r.max_new == 1:
                self._complete(lv, now)
            else:
                self.live[row] = lv
                self._tokens[row] = first[i]
                self._pos[row] = r.prompt_len

    def _admit(self, now: int) -> None:
        budget = self.policy.admit_budget(self.n_live)
        admitted = self.queue.pop_ready(now, budget)
        if not admitted:
            return
        with _obs.span("admit", n=len(admitted)):
            self._ensure_slab(self.n_live + len(admitted))
            groups: dict[int, list[Request]] = {}
            for r in admitted:
                groups.setdefault(
                    self.table.prompt_bucket(r.prompt_len), []
                ).append(r)
            for pb in sorted(groups):
                self._prefill_group(groups[pb], pb, now)

    # ------------------------------------------------------------ decode
    def _decode_all(self, now: int) -> None:
        step_fn = (functools.partial(engine.guarded_decode_step,
                                     step=self._decode)
                   if self.guard else self._decode)
        with _obs.span("decode", batch=len(self._tokens), live=len(self.live)):
            logits, self._slab = self._model_call(
                lambda: step_fn(
                    self.params,
                    self.cfg,
                    self._slab,
                    jnp.asarray(self._tokens),
                    jnp.asarray(self._pos),
                )
            )
        with _obs.span("sync"):
            tok = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            logits_np = np.asarray(logits) if self.trace_logits else None
        with _obs.span("bookkeep"):
            self._advance_rows(tok, logits_np, now)

    def _advance_rows(self, tok: np.ndarray, logits_np: np.ndarray | None,
                      now: int) -> None:
        self.telemetry.decode_steps += 1
        for row in sorted(self.live):
            lv = self.live[row]
            if logits_np is not None:
                self.logit_trace[lv.req.rid].append(logits_np[row])
            lv.generated.append(int(tok[row]))
            self.telemetry.tokens_out += 1
            self._tokens[row] = tok[row]
            self._pos[row] += 1
            if len(lv.generated) >= lv.req.max_new:
                self._complete(lv, now)

    def _complete(self, lv: _Live, now: int) -> None:
        self.live.pop(lv.row, None)
        self._free.release(lv.row)
        self._tokens[lv.row] = 0
        self._pos[lv.row] = 0
        self.results[lv.req.rid] = {
            "tokens": tuple(lv.generated),
            "ttft": lv.admit_tick - lv.req.arrival + 1,
            "latency": now - lv.req.arrival + 1,
        }
        self.telemetry.observe_completion(
            now - lv.req.arrival + 1, len(lv.generated)
        )

    # --------------------------------------------------------------- run
    def step(self) -> None:
        """One tick: admit + prefill, then one batched decode step."""
        now = self.clock.now
        with _obs.span("tick", f"t{now}", tick=now):
            self._admit(now)
            if self.live:
                self._decode_all(now)
        self.telemetry.ticks += 1
        self.clock.advance()

    def run(self, requests=None, max_ticks: int = 1000) -> dict[int, dict]:
        """Drive the loop until the stream drains (or max_ticks)."""
        for r in requests or ():
            self.submit(r)
        for _ in range(max_ticks):
            if not self.queue and not self.live:
                break
            self.step()
        self.telemetry.record_health()
        return self.results


def scripted_trace(
    entries, *, vocab_size: int, seed: int = 0
) -> list[Request]:
    """Deterministic arrival trace: entries of (arrival, prompt_len,
    max_new) become `Request`s with seeded-random prompt tokens.  No
    Poisson, no wall clock — the same entries always replay the same
    trace."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid, (arrival, prompt_len, max_new) in enumerate(entries):
        toks = tuple(int(t) for t in rng.integers(0, vocab_size, prompt_len))
        reqs.append(
            Request(rid=rid, tokens=toks, max_new=max_new, arrival=arrival)
        )
    return reqs
