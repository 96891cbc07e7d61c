"""Continuous-batching scheduler: FCFS admission + one batched engine step
per tick (paper §5.3.2's mixed prefill/decode workload).

Whole-prompt engines (prefill_chunk=0) admit at most one request per tick
(each admission is a blocking B=1 prefill) before the batched decode step.
Chunked engines admit every queued request that gets a slot — admission only
claims the slot — and the engine's token budget paces the prefill chunks
across the subsequent mixed steps; TTFT is then measured when a request's
*last* chunk completes and its first token is sampled. Ticks with no work
(no slot prefilling or decoding) skip the batched step entirely.

Pure-python control around the jit'd engine steps; per-request latency and
throughput accounting built in (used by benchmarks/decode_bench.py to
reproduce the paper's continuous-batching table).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Iterable

import jax

from .engine import (
    Engine,
    Request,
    spec_acceptance_rate,
    spec_mean_k,
    spec_nodes_per_step,
    spec_skip_rate,
    spec_tokens_per_step,
)

#: engine counters ServeStats mirrors; run_to_completion snapshots them so a
#: scheduler reused across runs reports per-run deltas, not lifetime totals
_ENGINE_COUNTERS = (
    "prefill_tokens", "prefill_pad_tokens", "decode_tokens", "decode_steps",
    "chunk_steps", "spec_steps", "spec_slot_steps",
    "spec_skipped_steps", "drafted_tokens", "accepted_tokens",
    "verified_nodes", "prefix_hit_tokens", "prefix_hit_requests",
)


@dataclasses.dataclass
class ServeStats:
    wall_s: float = 0.0
    prefill_tokens: int = 0         # real prompt tokens (padding excluded)
    prefill_pad_tokens: int = 0     # bucket/chunk padding, reported separately
    decode_tokens: int = 0
    decode_steps: int = 0           # batched decode/verify step invocations
    chunk_steps: int = 0            # batched mixed chunk-step invocations
    completed: int = 0
    rejected: int = 0               # failed admission (Request.error set)
    ttft_s: list = dataclasses.field(default_factory=list)
    # speculative decoding (zero when the engine runs without spec=)
    spec_steps: int = 0         # batched verify steps
    spec_slot_steps: int = 0    # per-slot verify steps (Σ active slots)
    spec_skipped_steps: int = 0  # slot steps that skipped drafting (k_eff=0)
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    verified_nodes: int = 0     # candidate tokens verified (Σ per slot)
    # paged KV + radix prefix sharing (zero when the engine runs unpaged)
    prefix_hit_tokens: int = 0   # prompt tokens served off shared pages
    prefix_hit_requests: int = 0  # admissions that hit the prefix index

    @property
    def total_tokens(self) -> int:
        return self.prefill_tokens + self.decode_tokens

    @property
    def acceptance_rate(self) -> float:
        return spec_acceptance_rate(self.accepted_tokens, self.drafted_tokens)

    @property
    def decode_tokens_per_step(self) -> float:
        return spec_tokens_per_step(self.decode_tokens, self.spec_slot_steps)

    @property
    def skip_rate(self) -> float:
        """Fraction of slot verify steps the adaptive policy left undrafted."""
        return spec_skip_rate(self.spec_skipped_steps, self.spec_slot_steps)

    @property
    def mean_draft_k(self) -> float:
        """Mean k_eff over the slot steps that did draft (k when fixed)."""
        return spec_mean_k(
            self.drafted_tokens, self.spec_slot_steps, self.spec_skipped_steps
        )

    @property
    def nodes_per_step(self) -> float:
        """Mean candidate tokens per slot verify row — the per-slot M the
        Vec-LUT kernels see (k+1 chain, the tree node count under trees)."""
        return spec_nodes_per_step(self.verified_nodes, self.spec_slot_steps)

    @property
    def throughput_tok_s(self) -> float:
        return self.total_tokens / self.wall_s if self.wall_s else 0.0

    @property
    def decode_tok_s(self) -> float:
        return self.decode_tokens / self.wall_s if self.wall_s else 0.0

    @property
    def prefill_tok_s(self) -> float:
        return self.prefill_tokens / self.wall_s if self.wall_s else 0.0


class ContinuousBatchingScheduler:
    def __init__(self, engine: Engine):
        self.engine = engine
        self.queue: deque[Request] = deque()
        self.completed: list[Request] = []  # finished requests, in finish order
        self.rejected: list[Request] = []   # failed admission (req.error set)
        # high-water marks of what earlier run_to_completion calls already
        # reported, so each run's ServeStats covers exactly the work since
        # the last report (manual ticks included) and never re-counts it
        self._reported = {k: getattr(engine, k) for k in _ENGINE_COUNTERS}
        self._reported_done = 0
        self._reported_rejected = 0
        self._reported_ttft = 0

    def submit(self, reqs: Iterable[Request]):
        for r in reqs:
            r.t_submit = time.perf_counter()
            self.queue.append(r)

    def tick(self):
        """One scheduler iteration: admissions + 1 batched engine step.

        Whole-prompt engines admit ≤1 request (each admission is a blocking
        B=1 prefill); chunked engines admit every queued request that gets a
        slot — claims are free, and the engine's token budget paces the
        prefill chunks across subsequent mixed steps.

        A request the engine can never fit (prompt + budget > max_len) is
        rejected in place — `error` set, `done` stays False, no output; see
        `self.rejected` — so one bad request aborts itself, not the batch.
        A rejection does not consume the tick's admission: the scheduler
        keeps trying subsequent queued requests until one admits, the engine
        reports no free slot, or the queue drains. A tick with nothing
        prefilling or decoding (every admission satisfied by prefill alone)
        skips the batched step instead of burning a dispatch on an empty
        batch."""
        obs = self.engine.obs
        with obs.span("scheduler_tick") as tick:
            with obs.span("scheduler.admit"):
                multi = bool(self.engine.prefill_chunk)
                while self.queue:
                    head = self.queue[0]
                    try:
                        if not self.engine.add(head):
                            break          # no free slot — head stays queued
                        self.queue.popleft()
                        if head.done:      # satisfied by prefill alone
                            self.completed.append(head)
                        if not multi:
                            break          # one blocking admission per tick
                    except ValueError as e:
                        head.error = str(e)
                        self.rejected.append(head)
                        self.queue.popleft()  # rejected in place; try the next
                before = list(self.engine.slot_req.values()) + list(
                    self.engine.prefilling.values()
                )
            if self.engine.has_work:
                self.engine.step()
            with obs.span("scheduler.finish"):
                for r in before:
                    if r.done:         # finished this step (decode or final
                        self.completed.append(r)  # chunk, max_new_tokens=1)
                if obs.enabled:
                    # end-of-tick state sync: queue depth + slot occupancy
                    # gauges, counter mirrors — the registry reads engine
                    # state, never double-counts it
                    obs.on_tick(
                        self.engine, queue_depth=len(self.queue),
                        completed=len(self.completed),
                        rejected=len(self.rejected),
                    )
                    tick.args.update(queue=len(self.queue),
                                     running=int(self.engine.active.sum()),
                                     prefilling=len(self.engine.prefilling))
                    if not self.engine.has_work:
                        # the next step waits on requests, not on the host
                        self.engine.last_sync_t = None

    def run_to_completion(self, max_ticks: int = 100_000) -> ServeStats:
        """Drain the queue (≤ max_ticks); → ServeStats for this run.

        Stats are per-run deltas against what earlier calls already
        reported: tokens/completions/rejections/TTFTs from manual ticks
        since the last report are included, but a reused scheduler/engine
        can never re-count an earlier run's work against the new run's
        wall clock (which used to inflate throughput and acceptance)."""
        t0 = time.perf_counter()
        # tolerate an external engine.reset_stats() between runs: count
        # from the reset point rather than going negative
        base = {
            k: min(self._reported[k], getattr(self.engine, k))
            for k in _ENGINE_COUNTERS
        }
        pending = lambda: self.queue or self.engine.has_work
        ticks = 0
        while pending() and ticks < max_ticks:
            self.tick()
            ticks += 1
        # drain async dispatch before stopping the clock: per-tick host
        # syncs (np.asarray on logits) cover most of it, but donated cache
        # updates can still be in flight and would under-report wall time
        jax.block_until_ready(self.engine.cache)
        wall = time.perf_counter() - t0
        # every request this scheduler has seen: finished (incl. by earlier
        # manual ticks), still in flight, and never admitted
        all_reqs: list[Request] = (
            self.completed
            + list(self.engine.slot_req.values())
            + list(self.engine.prefilling.values())
            + list(self.queue)
        )
        self._reported = {
            k: getattr(self.engine, k) for k in _ENGINE_COUNTERS
        }
        done = sum(r.done for r in all_reqs)
        # first-token latencies in event order, minus the already-reported
        # prefix (the event times are monotone across ticks)
        ttft_events = sorted(
            (r.t_first_token, r.t_first_token - r.t_submit)
            for r in all_reqs
            if r.t_first_token
        )
        stats = ServeStats(
            wall_s=wall,
            completed=done - self._reported_done,
            rejected=len(self.rejected) - self._reported_rejected,
            ttft_s=[d for _, d in ttft_events[self._reported_ttft:]],
            **{k: self._reported[k] - base[k] for k in _ENGINE_COUNTERS},
        )
        self._reported_done = done
        self._reported_rejected = len(self.rejected)
        self._reported_ttft = len(ttft_events)
        return stats
