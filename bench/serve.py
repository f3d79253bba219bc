"""Drive the serving engine with a traffic mix and stamp what users see.

The harness uses only the engine's public surface: ``ServingEngine(...)``,
``submit``, ``step``, ``slots``, ``n_host``, ``done`` and ``export_metrics``
(and, to warm up programs during set-up, the private hooks named in
``warm_up``). Its loop:

1. submit every request whose due time has passed;
2. call ``eng.step()`` (admission, prefill, one device loop of verify rounds,
   one host sync);
3. after the step, stamp each row's new tokens with the time the step
   returned: the first token when its ``n_host`` first exceeds its prompt
   length, every later token as it shows up at a sync;
4. with no work, sleep until the next due time.

``submit``, ``step`` and the sleep run inside ``TraceAnnotation`` spans
(``bench.submit``, ``bench.step``, ``bench.sleep``), which a traced run uses
to say what the host was doing while the device idled.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

from bench.traffic import Spec, Traffic

clock = time.monotonic


@dataclass
class Record:
    """One request's life as the harness saw it (host monotonic seconds)."""
    spec: Spec
    due: float
    req: object = None                 # the engine's Request
    submit_t: float = 0.0
    first_t: Optional[float] = None    # first output token stamped
    done_t: Optional[float] = None     # finished or failed
    n_seen: int = 0                    # accepted length at the last stamp
    stamps: list = field(default_factory=list)   # (time, tokens) per sync
    failed: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.spec.prompt)


@dataclass
class StepLog:
    """One ``eng.step()`` as a traced run needs it for operation counts."""
    t0: float
    t1: float
    rounds: int = 0                    # verify rounds the device loop ran
    prefill_calls: int = 0             # prefill chunks dispatched
    window: int = 0                    # W of that loop
    rows: list = field(default_factory=list)
    #                    (cached length at loop start, active rounds, tokens)
    prefills: list = field(default_factory=list)  # (start, end) positions


class Load:
    """Closed- or open-loop load on one engine, with stamps."""

    def __init__(self, eng, traffic: Traffic, make_request, *,
                 log_steps: bool = False):
        self.eng, self.traffic = eng, traffic
        self.make_request = make_request
        self.log_steps = log_steps
        self.records: dict[int, Record] = {}
        self.steps: list[StepLog] = []
        self.sleep_s = 0.0
        self.lateness: list[float] = []    # submit time - due time
        self._done_seen = len(eng.done)
        self._live: set[int] = set()        # submitted, not yet done
        self._queue: list[Record] = []     # due, not yet submitted
        self._next_arrival: Optional[float] = None
        self._started = False

    # -- arrivals -----------------------------------------------------------
    def _start(self, now: float):
        mix = self.traffic.mix
        if mix["loop"] == "closed":
            for _ in range(int(mix["clients"])):
                self._enqueue(self.traffic.next_spec(), now)
        else:
            self._next_arrival = now
        self._started = True

    def _enqueue(self, spec: Spec, due: float):
        self._queue.append(Record(spec, due))

    def _arrivals(self, now: float):
        while self._next_arrival is not None and self._next_arrival <= now:
            self._enqueue(self.traffic.next_spec(), self._next_arrival)
            self._next_arrival += self.traffic.next_gap()

    def next_due(self) -> Optional[float]:
        if self._queue:
            return min(r.due for r in self._queue)
        return self._next_arrival

    # -- the loop -----------------------------------------------------------
    def run(self, until: float):
        """Serve until the host clock reaches ``until``."""
        if not self._started:
            self._start(clock())
        while True:
            now = clock()
            if now >= until:
                return
            self._arrivals(now)
            if self._queue:
                with TraceAnnotation("bench.submit"):
                    for rec in self._queue:
                        rec.req = self.make_request(rec.spec)
                        rec.submit_t = clock()
                        self.lateness.append(rec.submit_t - rec.due)
                        self.records[rec.req.uid] = rec
                        self._live.add(rec.req.uid)
                        self.eng.submit(rec.req)
                    self._queue = []
            if self._live:
                self._step()
            else:
                nxt = self.next_due()
                wait = (until if nxt is None else min(nxt, until)) - clock()
                if wait > 0:
                    with TraceAnnotation("bench.sleep"):
                        time.sleep(wait)
                    self.sleep_s += wait

    def _step(self):
        eng = self.eng
        before = {}
        if self.log_steps:
            m0 = eng.export_metrics()
            for b, r in enumerate(eng.slots):
                if r is not None:
                    before[r.uid] = (int(eng.n_host[b]), r.calls_used)
        t0 = clock()
        with TraceAnnotation("bench.step"):
            eng.step()
        t = clock()
        log = StepLog(t0, t) if self.log_steps else None
        if log is not None:
            m = eng.export_metrics()
            log.rounds = m["rounds"] - m0["rounds"]
            log.prefill_calls = m["prefill_calls"] - m0["prefill_calls"]
            log.window = int(m["window_final"])
        for b, r in enumerate(eng.slots):
            if r is not None:
                self._stamp(r, int(eng.n_host[b]), t, before, log)
        done = eng.done
        for r in done[self._done_seen:]:
            rec = self.records.get(r.uid)
            if rec is None:
                continue
            if r.ok:
                self._stamp(r, len(r.result), t, before, log)
            else:
                rec.failed = True
            rec.done_t = t
            self._live.discard(r.uid)
            if self.traffic.mix["loop"] == "closed":
                self._enqueue(self.traffic.next_spec(), t)
        self._done_seen = len(done)
        if log is not None:
            self.steps.append(log)

    def _stamp(self, r, n: int, t: float, before: dict, log):
        rec = self.records.get(r.uid)
        if rec is None:
            return
        if log is not None:
            if r.uid in before:
                n0, c0 = before[r.uid]
            else:                       # admitted in this step
                n0, c0 = rec.prompt_len, 0
                start = r.prefix_hit_blocks * self.eng.block_size
                log.prefills.append((start, rec.prompt_len - 1))
            log.rows.append((n0 - 1, r.calls_used - c0, n - n0))
        prev = max(rec.n_seen, rec.prompt_len)
        if n > prev:
            if rec.first_t is None:
                rec.first_t = t
            rec.stamps.append((t, n - prev))
            rec.n_seen = n


def warm_up(eng, traffic: Traffic, ks=(1, 4)):
    """Compile, during set-up, every program the cell's traffic can make the
    engine run: the round loop at each window W and loop length k, the
    prefill at each chunk width, and the small per-length ops of admission
    and harvest at every prompt and final length the mix sends. These hooks
    are private to the engine; where one is missing the step is skipped and
    its programs compile on first use."""
    import jax
    import jax.numpy as jnp

    rf = getattr(eng, "_round_loop_fn", None)
    ra = getattr(eng, "_round_args", None)
    if rf is not None and ra is not None:
        # the adaptive window takes powers of two from 2 to window_max:
        # every active row accepts at least one token a round, so the
        # accept average is at least 1 and the proposed W at least
        # round(1.7 x 1) = 2 (serving/adaptive.py)
        W = 2
        while W <= eng.W_max:
            for k in ks:
                rf(W, k).lower(*ra()).compile()
            W *= 2
    pf = getattr(eng, "_prefill_fn", None)
    if pf is not None:
        c = 1
        while c <= getattr(eng, "prefill_chunk", 64):
            pf(c).lower(eng.params, eng.paged,
                        jnp.zeros((1, eng.nb), jnp.int32),
                        jnp.zeros((1,), jnp.int32),
                        jnp.zeros((1, c), jnp.int32),
                        jnp.zeros((1,), jnp.int32)).compile()
            c *= 2
    tok = getattr(eng, "tokens", None)
    if tok is not None:
        for L in traffic.prompt_lengths():
            # as admission writes it: an int64 prompt converted on the
            # device (serving/engine.py)
            jax.block_until_ready(tok.at[0].set(0).at[0, :L].set(
                jnp.asarray(np.zeros(L, np.int64), jnp.int32)))
        for n in traffic.final_lengths():
            np.asarray(tok[0, :n])
