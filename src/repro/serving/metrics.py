"""Serving telemetry: per-request and engine-level counters as plain dicts,
and a bounded log of host spans.

No external metrics dependency — everything exports to ``dict`` so callers
can feed dashboards, benchmark tables, or test assertions directly. The
engine updates these from values it already syncs to host each round, so
telemetry adds no extra device round-trips.

Spans (:class:`SpanLog`) time the phases of the engine's host work on the
``time.monotonic`` clock of ``Request.submit_time``/``admit_time``. Each
span is also a ``jax.profiler.TraceAnnotation``, so a profile shows it on
the host plane, on the same timeline as the device's operations.
"""
from __future__ import annotations

import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

import numpy as np
from jax.profiler import TraceAnnotation

SPAN_LOG_SIZE = 2 ** 16             # records kept; older ones are dropped


def percentile(values, p: float) -> float:
    """p in [0, 100]; 0.0 on empty input (missing-data sentinel)."""
    vals = [v for v in values if v is not None]
    if not vals:
        return 0.0
    return float(np.percentile(np.asarray(vals, np.float64), p))


@dataclass
class EngineMetrics:
    rounds: int = 0                      # batch-level verify rounds (ARM calls)
    prefill_calls: int = 0               # row-local prefill chunk passes
    host_syncs: int = 0                  # stats-array pulls (one per loop)
    device_dispatches: int = 0           # round-loop program launches
    tokens_generated: int = 0
    tokens_accepted_hist: list = field(default_factory=list)  # per-loop sums
    occupancy_hist: list = field(default_factory=list)  # row-rounds/(rounds*B)
    active_row_rounds: int = 0           # (row, round) pairs active, total
    row_rounds: int = 0                  # rounds * batch, total — the
    #                                      duration-weighted occupancy
    #                                      denominator (the per-loop hist
    #                                      mean overweights short loops)
    window_hist: list = field(default_factory=list)           # W per loop
    requests_finished: int = 0
    request_latencies: list = field(default_factory=list)
    request_queue_waits: list = field(default_factory=list)
    request_calls: list = field(default_factory=list)         # rounds/request
    request_new_tokens: list = field(default_factory=list)
    deadline_miss_count: int = 0         # finished past their latency SLO
    deadline_requests: int = 0           # finished requests that carried one
    deadline_missed_in_queue: int = 0    # SLO expired while queued/parked
    #                                      (detected at admission poll time,
    #                                      once per request)
    preemptions: int = 0                 # slots parked for a higher priority
    resumes: int = 0                     # parked requests re-admitted
    migrations: int = 0                  # mid-flight slot/shard moves
    blocks_parked: int = 0               # block payloads spilled to host
    blocks_migrated: int = 0             # blocks device-copied across shards
    head_bypass_admissions: int = 0      # lookahead admissions past the head
    host_staged_blocks: int = 0          # KV blocks re-admitted from the host
    #                                      tier at admission (H2D staging)
    rec_snapshot_captures: int = 0       # recurrent-state rows checkpointed
    #                                      into the host tier at block bounds
    rec_snapshot_restores: int = 0       # admissions that resumed from a
    #                                      host-tier recurrent snapshot
    requests_failed: int = 0             # requests finished with a
    #                                      RequestError (quarantine/abort)
    requests_cancelled: int = 0          # requests cancelled via cancel(uid)
    requests_rejected: int = 0           # submit-time validation rejections
    retries: int = 0                     # failed requests re-admitted
    staging_errors: int = 0              # H2D staging runs aborted mid-ring
    resume_recomputes: int = 0           # parked resumes rebuilt by cold
    #                                      re-prefill (payload lost/corrupt)
    in_loop_adoptions: int = 0           # sequences adopted by a freed row
    #                                      inside the device loop (no sync)
    staged_sequences: int = 0            # requests ever staged for adoption
    staging_occupancy_hist: list = field(default_factory=list)  # staged/S
    #                                      per dispatch (drain-rate signal)
    prefetch_hits: int = 0               # queued requests whose host-tier
    #                                      prefix was restaged before admit
    idle_row_rounds: int = 0             # (row, round) pairs a freed row sat
    #                                      with the staging area drained
    recovered_requests: int = 0          # requests re-admitted from the
    #                                      journal by restore() (§16)
    recovered_parked: int = 0            # of those, resumed from a durable
    #                                      parked-sequence checkpoint (the
    #                                      rest re-prefill from scratch)
    checkpoints_written: int = 0         # scheduler snapshots fsynced at
    #                                      sync boundaries
    active_rr_backlog: int = 0           # the two counters above, restricted
    row_rr_backlog: int = 0              # to loops DISPATCHED with host
    #                                      backlog (queued or staged work
    #                                      waiting) — the §15 saturation
    #                                      claim is about these loops; the
    #                                      drain tail idles identically for
    #                                      every engine and only adds noise

    def _per_token(self, value: float) -> float:
        """All ``*_per_token`` exports divide here: 0.0 before the first
        generated token instead of ZeroDivisionError (a server exporting
        telemetry right after boot has tokens_generated == 0)."""
        return value / self.tokens_generated if self.tokens_generated else 0.0

    def observe_loop(self, window: int, rounds: int, active_row_rounds: int,
                     batch: int, accepted: int, backlog: int = 0):
        """One device-resident round loop (one dispatch, one host sync)
        covering ``rounds`` verify rounds; ``active_row_rounds`` counts
        (row, round) pairs in which the row was active. ``backlog`` is the
        host-side work (queued + staged) waiting when the loop was
        dispatched — loops with ``backlog > 0`` feed the under-backlog
        occupancy split."""
        self.rounds += int(rounds)
        self.host_syncs += 1
        self.device_dispatches += 1
        self.window_hist.append(int(window))
        self.active_row_rounds += int(active_row_rounds)
        self.row_rounds += max(1, int(rounds)) * batch
        if backlog > 0:
            self.active_rr_backlog += int(active_row_rounds)
            self.row_rr_backlog += max(1, int(rounds)) * batch
        denom = max(1, int(rounds)) * batch
        self.occupancy_hist.append(active_row_rounds / denom if batch
                                   else 0.0)
        self.tokens_accepted_hist.append(int(accepted))
        self.tokens_generated += int(accepted)

    def observe_finish(self, req):
        self.requests_finished += 1
        self.request_latencies.append(req.latency)
        self.request_queue_waits.append(req.queue_wait)
        self.request_calls.append(req.calls_used)
        self.request_new_tokens.append(req.new_tokens)
        if getattr(req, "deadline", None) is not None:
            self.deadline_requests += 1
            if req.missed_deadline:
                self.deadline_miss_count += 1

    def export(self, block_stats: dict | None = None,
               host_stats: dict | None = None) -> dict:
        calls = np.asarray(self.request_calls, np.float64)
        new = np.asarray(self.request_new_tokens, np.float64)
        out = {
            "rounds": self.rounds,
            "prefill_calls": self.prefill_calls,
            "host_syncs": self.host_syncs,
            "device_dispatches": self.device_dispatches,
            # device residency: verify rounds amortized per program launch /
            # per host pull (1.0 = host-driven; rounds_per_sync at best)
            "rounds_per_sync": (self.rounds / self.host_syncs
                                if self.host_syncs else 0.0),
            "dispatches_per_token": self._per_token(self.device_dispatches),
            "host_syncs_per_token": self._per_token(self.host_syncs),
            "syncs_per_token": self._per_token(self.host_syncs),
            "rounds_per_token": self._per_token(self.rounds),
            "tokens_generated": self.tokens_generated,
            "requests_finished": self.requests_finished,
            # hist entries are per-LOOP sums since the device-resident
            # rounds; normalize by executed rounds so the value keeps its
            # per-round meaning across rounds_per_sync settings
            "mean_accept_per_round": (self.tokens_generated / self.rounds
                                      if self.rounds else 0.0),
            "mean_batch_occupancy": (
                float(np.mean(self.occupancy_hist))
                if self.occupancy_hist else 0.0),
            # duration-weighted occupancy: active row-rounds over ALL row-
            # rounds executed — the per-loop mean above weights a 1-round
            # loop equally with an 8-round one, which misranks engines that
            # run different loop lengths for the same work
            "occupancy_weighted": (self.active_row_rounds / self.row_rounds
                                   if self.row_rounds else 0.0),
            # saturation while work waits (§15): 1.0 means no (row, round)
            # pair was wasted while the host held adoptable work. The k=1
            # host-admission baseline is 1.0 here BY CONSTRUCTION (it syncs
            # every round, so refill is instant); a device-resident loop
            # can only approach it, paying <= 1 round of idle per freed row
            # before adoption or the starvation exit kicks in
            "occupancy_under_backlog": (
                self.active_rr_backlog / self.row_rr_backlog
                if self.row_rr_backlog else 0.0),
            "mean_window": (float(np.mean(self.window_hist))
                            if self.window_hist else 0.0),
            "window_final": self.window_hist[-1] if self.window_hist else 0,
            "arm_calls_per_request_mean": (
                float(calls.mean()) if calls.size else 0.0),
            # < 1.0 means speculation beat ancestral decode
            "arm_calls_vs_ancestral": (
                float((calls / np.maximum(new, 1)).mean())
                if calls.size else 0.0),
            "latency_p50_s": percentile(self.request_latencies, 50),
            "latency_p95_s": percentile(self.request_latencies, 95),
            "queue_wait_p50_s": percentile(self.request_queue_waits, 50),
            "queue_wait_p95_s": percentile(self.request_queue_waits, 95),
            "deadline_miss_count": self.deadline_miss_count,
            "deadline_requests": self.deadline_requests,
            "deadline_missed_in_queue": self.deadline_missed_in_queue,
            "preemptions": self.preemptions,
            "resumes": self.resumes,
            "migrations": self.migrations,
            "blocks_parked": self.blocks_parked,
            "blocks_migrated": self.blocks_migrated,
            "head_bypass_admissions": self.head_bypass_admissions,
            "host_staged_blocks": self.host_staged_blocks,
            "rec_snapshot_captures": self.rec_snapshot_captures,
            "rec_snapshot_restores": self.rec_snapshot_restores,
            "requests_failed": self.requests_failed,
            "requests_cancelled": self.requests_cancelled,
            "requests_rejected": self.requests_rejected,
            "retries": self.retries,
            "staging_errors": self.staging_errors,
            "resume_recomputes": self.resume_recomputes,
            "in_loop_adoptions": self.in_loop_adoptions,
            "staged_sequences": self.staged_sequences,
            "staging_occupancy": (
                float(np.mean(self.staging_occupancy_hist))
                if self.staging_occupancy_hist else 0.0),
            "prefetch_hits": self.prefetch_hits,
            "idle_row_rounds": self.idle_row_rounds,
            "recovered_requests": self.recovered_requests,
            "recovered_parked": self.recovered_parked,
            "checkpoints_written": self.checkpoints_written,
        }
        if block_stats:
            out.update(block_stats)
        if host_stats:
            # arena + staging-ring counters (host_hits/host_evictions/
            # bytes_resident/h2d_staged/h2d_overlap_frac, ...)
            out.update(host_stats)
        return out


class Span(NamedTuple):
    """One closed span of host time (``time.monotonic`` seconds)."""
    name: str
    t0: float
    t1: float
    step: Optional[int]         # the recording engine's step number
    uid: Optional[int]          # request id, on request-scoped spans
    engine: Optional[int]       # small integer naming the recording engine


class SpanLog:
    """A bounded log of host spans: the last ``maxlen`` closed spans, in
    the order they closed (children before their parent).

    Recording is always on: with the profiler off a span costs a few
    microseconds of host time (PERF.md, "Spans and scopes")."""

    def __init__(self, maxlen: int = SPAN_LOG_SIZE):
        self.records: deque[Span] = deque(maxlen=maxlen)

    @contextmanager
    def span(self, name: str, *, step: Optional[int] = None,
             uid: Optional[int] = None, engine: Optional[int] = None):
        """``with log.span("serve.admit", step=s): ...`` records the
        block's host time under ``name`` (also when it raises), inside a
        ``TraceAnnotation`` of the same name carrying the ids."""
        ids = {k: v for k, v in (("step", step), ("uid", uid),
                                 ("engine", engine)) if v is not None}
        with TraceAnnotation(name, **ids):
            t0 = time.monotonic()
            try:
                yield
            finally:
                self.records.append(
                    Span(name, t0, time.monotonic(), step, uid, engine))

    def spans(self, t0: float, t1: float) -> list[Span]:
        """The kept spans that start at or after ``t0`` and end at or
        before ``t1``, in the order they closed."""
        return [s for s in self.records if s.t0 >= t0 and s.t1 <= t1]


_DEFAULT_LOG = SpanLog()


def default_span_log() -> SpanLog:
    """The process's span log, shared by every engine (as ``logging``'s
    root logger is): readers reach the spans after the engine that
    recorded them is gone."""
    return _DEFAULT_LOG
