"""Fault-isolated serving system tests (DESIGN.md §14).

The acceptance bar: under a scripted :class:`FaultPlan` — injected alloc
failures, arena corruption, staging drops, a NaN-poisoned noise stream —
the engine finishes every *healthy* request with tokens bitwise equal to
the fault-free run, fails only the targeted requests with structured
:class:`RequestError`\\ s, and (with a retry budget) recovers even those:
capacity faults replay the same stream exactly, quarantined rows get a
fresh stream. Corruption and staging faults are never errors at all — the
integrity check demotes them to cache misses and the engine recomputes
(cold resume), still bit-exact. ``cancel(uid)`` removes a request wherever
it lives; wall-time / round budgets bound runaways."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.engine import PredictiveSampler
from repro.models.transformer import TransformerLM
from repro.serving import (CircuitBreaker, FaultPlan, HostArena, HostTier,
                           Request, ServingEngine, StagingRing)
from repro.serving.faults import SEAMS, StagingFault

EPS_KEY = jax.random.PRNGKey(9)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen3-1.7b", reduced=True)
    params = TransformerLM.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo(cfg, params, req, window, max_len):
    s = PredictiveSampler(cfg, params, window=window, max_len=max_len,
                          eps_key=EPS_KEY)
    t, _ = s.generate(np.asarray(req.prompt)[None].astype(np.int32),
                      req.new_tokens,
                      seq_ids=np.asarray([req.seq_id], np.int32))
    return np.asarray(t[0, :len(req.prompt) + req.new_tokens])


def _traffic(cfg, rng_seed=3, n=4, lo=2, hi=7, new_lo=8, new_hi=12):
    rng = np.random.default_rng(rng_seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=int(rng.integers(lo, hi))),
                    new_tokens=int(rng.integers(new_lo, new_hi)))
            for i in range(n)]


# -- harness units (no engine) ----------------------------------------------

def test_fault_plan_parse_and_deterministic_replay():
    plan = FaultPlan.parse("seed=7,alloc=@2;5,arena_corrupt=0.25,poison=3;9")
    assert plan.schedule["alloc"] == frozenset({2, 5})
    assert plan.rates["arena_corrupt"] == 0.25
    assert plan.seed == 7 and plan.poison_streams == frozenset({3, 9})
    # explicit indices fire exactly at the scripted invocations
    fires = [plan.fire("alloc") for _ in range(8)]
    assert fires == [False, False, True, False, False, True, False, False]
    assert plan.fired["alloc"] == 2 and plan.calls["alloc"] == 8
    # seeded rates replay bit-identically across plan instances (the CI
    # chaos job re-parses the same spec in every process)
    a = FaultPlan.parse("seed=7,arena_corrupt=0.25")
    b = FaultPlan.parse("seed=7,arena_corrupt=0.25")
    seq = [a.fire("arena_corrupt") for _ in range(400)]
    assert seq == [b.fire("arena_corrupt") for _ in range(400)]
    assert 0 < sum(seq) < 400          # the rate actually does something
    c = FaultPlan.parse("seed=8,arena_corrupt=0.25")
    assert seq != [c.fire("arena_corrupt") for _ in range(400)]
    # no plan / unknown seam
    assert FaultPlan.parse("") is None and FaultPlan.parse("  ") is None
    with pytest.raises(AssertionError):
        FaultPlan.parse("bogus_seam=@1")
    # a seam with no schedule never fires
    assert not any(plan.fire("stage_drop") for _ in range(50))
    assert plan.total_fired == 2


def test_fault_plan_from_env(monkeypatch):
    monkeypatch.delenv("REPRO_FAULT_PLAN", raising=False)
    assert FaultPlan.from_env() is None
    monkeypatch.setenv("REPRO_FAULT_PLAN", "seed=3,stage_drop=0.5,poison=4")
    plan = FaultPlan.from_env()
    assert plan.rates["stage_drop"] == 0.5
    assert plan.poison_streams == frozenset({4})
    assert set(plan.schedule) <= set(SEAMS)


def test_circuit_breaker_trip_cooldown_halfopen_cycle():
    br = CircuitBreaker(threshold=3, cooldown=4)
    # failures must be CONSECUTIVE to trip
    br.record_failure(); br.record_failure(); br.record_success()
    br.record_failure(); br.record_failure()
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.state == "open" and br.trips == 1
    # open: denied for cooldown-1 ops, then the half-open probe passes
    assert [br.allow() for _ in range(3)] == [False, False, False]
    assert br.denied == 3
    assert br.allow() and br.state == "half_open"
    br.record_failure()                       # probe failed: re-open
    assert br.state == "open" and br.trips == 2
    for _ in range(3):
        br.allow()
    assert br.allow() and br.state == "half_open"
    br.record_success()                       # probe succeeded: re-close
    assert br.state == "closed"
    assert br.stats_export() == {"tier_state": "closed", "tier_tripped": 2,
                                 "tier_denied_ops": 6}


def test_arena_corruption_is_a_miss_never_an_error():
    seen = []
    a = HostArena(1 << 16, faults=FaultPlan(schedule={"arena_corrupt": (1,)}),
                  on_corruption=seen.append)
    blk = np.arange(32, dtype=np.float32).reshape(4, 8)
    assert a.put("k", [blk])
    np.testing.assert_array_equal(a.get("k")[0], blk)   # invocation 0: clean
    assert a.get("k") is None          # invocation 1: corrupted -> dropped
    assert seen == ["k"]
    assert a.stats.checksum_failures == 1
    assert not a.contains("k")         # corrupt bytes never served again
    # a PINNED corrupt entry is dropped too (a corrupt pin protects nothing)
    a2 = HostArena(1 << 16, faults=FaultPlan(schedule={"arena_corrupt": (0,)}),
                   on_corruption=seen.append)
    a2.put("p", [blk], pin=True)
    assert a2.get("p") is None and not a2.contains("p")
    a2.unpin("p")                      # owner's unpin stays a safe no-op
    # integrity off: the seam still fires but nothing verifies (A/B lane)
    a3 = HostArena(1 << 16, integrity=False,
                   faults=FaultPlan(schedule={"arena_corrupt": (0,)}))
    a3.put("k", [blk])
    assert a3.get("k") is not None and a3.stats.checksum_failures == 0


def test_tripped_tier_answers_every_probe_as_a_miss():
    t = HostTier(1 << 16, breaker=CircuitBreaker(threshold=1, cooldown=100))
    blk = np.ones((4, 8), np.float32)
    assert t.put_kv(0, 11, [blk]) and t.put_park(5, [blk])
    t.record_failure()                 # threshold=1: open immediately
    assert not t.put_kv(0, 12, [blk])
    assert t.get_kv(0, 11) is None and not t.has_kv(0, 11)
    assert t.kv_run(0, [11]) == 0 and t.take_park(5) is None
    assert not t.pin_kv(0, 11)
    # refcount hygiene is never breaker-gated
    t.unpin_kv(0, 11)
    assert t.drop_park(5)
    st = t.stats_export()
    assert st["tier_state"] == "open" and st["tier_tripped"] == 1
    assert st["tier_denied_ops"] >= 6


def test_staging_drop_raises_and_clear_leaves_nothing():
    ring = StagingRing(depth=2,
                       faults=FaultPlan(schedule={"stage_drop": (1,)}))
    blk = np.zeros((4, 8), np.float32)
    ring.stage(("t0", 0), [blk])
    with pytest.raises(StagingFault):
        ring.stage(("t1", 1), [blk])
    assert ring.clear() == 1           # the in-flight upload is dropped
    assert ring.take() is None         # nothing staged for a later caller
    st = ring.stats_export()
    assert st["h2d_dropped"] == 1


# -- engine: quarantine + retry (the tentpole acceptance) --------------------

def test_injected_alloc_fault_fails_only_offending_request(qwen):
    """The first block allocation dies (seam ``alloc`` @0) during the first
    admission: with no retry budget that request finishes with a structured
    retryable 'admission' error, every other request's tokens are bitwise
    those of the fault-free engine AND of solo runs."""
    cfg, params = qwen
    kw = dict(batch=2, window_max=4, max_len=48, eps_key=EPS_KEY,
              block_size=4, adaptive=False)

    def run(faults, retries=0):
        eng = ServingEngine(cfg, params, faults=faults,
                            request_retries=retries, **kw)
        for r in _traffic(cfg):
            assert eng.submit(r)
        return {r.uid: r for r in eng.run()}, eng

    ref, _ = run(FaultPlan())          # empty plan == fault-free
    got, eng = run(FaultPlan(schedule={"alloc": (0,)}))
    assert eng.faults.fired == {"alloc": 1}
    assert eng.export_metrics()["faults_injected"] == 1
    failed = [r for r in got.values() if not r.ok]
    assert len(failed) == 1
    err = failed[0].error
    assert err.code == "admission" and err.retryable and err.attempts == 1
    assert "MemoryError" in err.detail and failed[0].result is None
    assert eng.metrics.requests_failed == 1
    for uid, r in got.items():
        if r.ok:
            np.testing.assert_array_equal(
                r.result, ref[uid].result,
                err_msg=f"healthy request {uid} diverged under faults")
            np.testing.assert_array_equal(
                r.result, _solo(cfg, params, r, 4, 48))


def test_retry_after_capacity_fault_is_bit_exact(qwen):
    """A retryable capacity fault under the retry budget replays the SAME
    noise stream from a fresh admission — chunked-prefill invariance makes
    the retried run bitwise identical to the never-faulted one."""
    cfg, params = qwen
    kw = dict(batch=2, window_max=4, max_len=48, eps_key=EPS_KEY,
              block_size=4, adaptive=False)
    ref = ServingEngine(cfg, params, **kw)
    for r in _traffic(cfg):
        ref.submit(r)
    ref_res = {r.uid: r.result for r in ref.run()}

    eng = ServingEngine(cfg, params, request_retries=1,
                        faults=FaultPlan(schedule={"alloc": (0, 3)}), **kw)
    reqs = _traffic(cfg)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert all(r.ok for r in done), [str(r.error) for r in done if r.error]
    assert eng.metrics.retries >= 1
    assert sum(r.retries for r in reqs) >= 1
    for r in done:
        np.testing.assert_array_equal(
            r.result, ref_res[r.uid],
            err_msg=f"retried request {r.uid} lost exactness")


def test_poisoned_stream_is_quarantined_rest_of_batch_exact(qwen):
    """A NaN-poisoned noise stream (seam ``poison``, injected at the LOGITS
    level on device) trips the packed-stats health bit: that row alone is
    failed with code 'nonfinite', its blocks released, and the OTHER rows of
    the same device batch finish bitwise equal to the fault-free run —
    the §14 quarantine contract."""
    cfg, params = qwen
    kw = dict(batch=2, window_max=4, max_len=48, eps_key=EPS_KEY,
              block_size=4, adaptive=False)
    ref = ServingEngine(cfg, params, **kw)
    for r in _traffic(cfg):
        ref.submit(r)
    ref_res = {r.uid: r.result for r in ref.run()}

    eng = ServingEngine(cfg, params, faults=FaultPlan(poison_streams=(2,)),
                        **kw)
    for r in _traffic(cfg):
        eng.submit(r)
    got = {r.uid: r for r in eng.run()}
    bad = got[2]
    assert not bad.ok and bad.result is None
    assert bad.error.code == "nonfinite" and bad.error.retryable
    assert "health bits" in bad.error.detail
    assert eng.metrics.requests_failed == 1
    for uid in (0, 1, 3):
        assert got[uid].ok
        np.testing.assert_array_equal(
            got[uid].result, ref_res[uid],
            err_msg=f"request {uid} shared a batch with the poisoned row")


def test_quarantine_retry_uses_a_fresh_noise_stream(qwen):
    """With a retry budget, the quarantined request re-admits on a FRESH
    noise stream (replaying the poisoned one would fail identically) and
    completes; its tokens match a solo run keyed by the new stream."""
    cfg, params = qwen
    kw = dict(batch=2, window_max=4, max_len=48, eps_key=EPS_KEY,
              block_size=4, adaptive=False)
    eng = ServingEngine(cfg, params, request_retries=1,
                        faults=FaultPlan(poison_streams=(2,)), **kw)
    reqs = _traffic(cfg)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert all(r.ok for r in done), [str(r.error) for r in done if r.error]
    poisoned = next(r for r in reqs if r.uid == 2)
    assert poisoned.retries == 1
    assert poisoned.noise_seed is not None
    assert poisoned.seq_id not in eng.faults.poison_streams
    for r in done:                     # incl. the re-streamed row
        np.testing.assert_array_equal(
            r.result, _solo(cfg, params, r, 4, 48),
            err_msg=f"request {r.uid} diverged from its solo run")


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "jamba-1.5-large-398b"])
def test_corrupted_park_falls_back_to_cold_resume_exact(arch):
    """Every arena read corrupted (rate 1.0): parked payloads and pinned
    prefix entries all demote to misses, resume goes down the cold
    recompute path (chunk decomposition is bitwise-invariant), and the
    preempted request still matches its undisturbed run — for attention
    AND the recurrent hybrid (snapshot gone -> rebuild from zero)."""
    cfg = get_config(arch, reduced=True)
    params = TransformerLM.init(jax.random.PRNGKey(0), cfg)
    kw = dict(batch=1, window_max=4, max_len=96, eps_key=EPS_KEY,
              block_size=4, adaptive=False)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab, 9)
    hi_prompt = rng.integers(0, cfg.vocab, 3)

    def run(faults):
        eng = ServingEngine(cfg, params, faults=faults, **kw)
        lo = Request(uid=0, prompt=prompt, new_tokens=40, priority=5)
        hi = Request(uid=1, prompt=hi_prompt, new_tokens=6, priority=0)
        eng.submit(lo)
        eng.step()
        eng.submit(hi)                 # higher priority -> park lo
        done = {r.uid: r for r in eng.run()}
        assert eng.metrics.preemptions == 1
        return done, eng

    ref, _ = run(FaultPlan())
    got, eng = run(FaultPlan(rates={"arena_corrupt": 1.0}))
    assert all(r.ok for r in got.values())
    assert eng.metrics.resume_recomputes >= 1
    m = eng.export_metrics()
    assert m["checksum_failures"] >= 1
    for uid in ref:
        np.testing.assert_array_equal(
            got[uid].result, ref[uid].result,
            err_msg=f"request {uid} diverged across the cold resume")


def test_staging_and_put_faults_stay_invisible_to_tokens(qwen):
    """``arena_put`` rejections (spill/park lost) and ``stage_drop`` ring
    deaths are pure de-optimizations: same preemption traffic, every token
    bitwise equal, failures only visible in the §14 counters."""
    cfg, params = qwen
    kw = dict(batch=1, window_max=4, max_len=96, eps_key=EPS_KEY,
              block_size=4, adaptive=False, host_cache_mb=8)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab, 13)
    hi_prompt = rng.integers(0, cfg.vocab, 3)

    def run(faults):
        eng = ServingEngine(cfg, params, faults=faults, **kw)
        lo = Request(uid=0, prompt=prompt, new_tokens=40, priority=5)
        hi = Request(uid=1, prompt=hi_prompt, new_tokens=6, priority=0)
        eng.submit(lo)
        eng.step()
        eng.submit(hi)
        done = {r.uid: r for r in eng.run()}
        assert eng.metrics.preemptions == 1
        return done, eng

    ref, _ = run(FaultPlan())
    got, eng = run(FaultPlan(rates={"arena_put": 1.0, "stage_drop": 1.0}))
    assert all(r.ok for r in got.values())
    assert eng.faults.total_fired >= 1
    for uid in ref:
        np.testing.assert_array_equal(got[uid].result, ref[uid].result)


# -- lifecycle: cancel / runaway bounds / validation -------------------------

def test_cancel_queued_running_and_parked(qwen):
    cfg, params = qwen
    kw = dict(batch=1, window_max=4, max_len=96, eps_key=EPS_KEY,
              block_size=4, adaptive=False)
    eng = ServingEngine(cfg, params, **kw)
    rng = np.random.default_rng(6)
    lo = Request(uid=0, prompt=rng.integers(0, cfg.vocab, 5), new_tokens=40,
                 priority=5)
    hi = Request(uid=1, prompt=rng.integers(0, cfg.vocab, 3), new_tokens=6,
                 priority=0)
    queued = Request(uid=2, prompt=rng.integers(0, cfg.vocab, 4),
                     new_tokens=8, priority=9)
    eng.submit(lo)
    eng.step()
    eng.submit(hi)                     # parks lo (priority preemption)
    eng.submit(queued)
    eng.step()
    assert eng.metrics.preemptions == 1 and 0 in eng.parked
    assert not eng.cancel(99)          # unknown uid
    assert eng.cancel(0)               # parked: queue entry + park discarded
    assert 0 not in eng.parked
    assert eng.cancel(2)               # still queued, never admitted
    running = next(b for b in range(1) if eng.slots[b] is not None)
    assert eng.slots[running].uid == 1
    assert eng.cancel(1)               # running: slot freed immediately
    assert eng.slots[running] is None
    done = {r.uid: r for r in eng.run()}
    assert set(done) == {0, 1, 2}
    assert all(r.error.code == "cancelled" and r.result is None
               for r in done.values())
    m = eng.export_metrics()
    assert m["requests_cancelled"] == 3 and m["parked_requests"] == 0
    assert m["blocks_in_use"] == 0     # cancelled rows released everything


def test_cancelled_neighbor_leaves_survivors_exact(qwen):
    cfg, params = qwen
    kw = dict(batch=2, window_max=4, max_len=48, eps_key=EPS_KEY,
              block_size=4, adaptive=False)
    ref = ServingEngine(cfg, params, **kw)
    for r in _traffic(cfg, n=3):
        ref.submit(r)
    ref_res = {r.uid: r.result for r in ref.run()}

    eng = ServingEngine(cfg, params, **kw)
    for r in _traffic(cfg, n=3):
        eng.submit(r)
    eng.step()
    assert eng.cancel(0)               # mid-flight, batch-mate of uid 1
    got = {r.uid: r for r in eng.run()}
    assert got[0].error.code == "cancelled"
    for uid in (1, 2):
        np.testing.assert_array_equal(got[uid].result, ref_res[uid])


def test_round_budget_and_wall_time_abort_runaways(qwen):
    cfg, params = qwen
    kw = dict(batch=1, window_max=4, max_len=64, eps_key=EPS_KEY,
              block_size=4, adaptive=False)
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab, 4)

    eng = ServingEngine(cfg, params, max_request_rounds=1, **kw)
    eng.submit(Request(uid=0, prompt=prompt, new_tokens=32))
    done = eng.run()
    assert done[0].error is not None and done[0].error.code == "round_budget"
    assert not done[0].error.retryable  # determinism: a retry would loop

    eng = ServingEngine(cfg, params, max_request_seconds=0.0, **kw)
    eng.submit(Request(uid=0, prompt=prompt, new_tokens=32))
    done = eng.run()
    assert done[0].error is not None and done[0].error.code == "timeout"
    assert eng.export_metrics()["requests_failed"] == 1


def test_submit_validation_rejects_malformed_requests(qwen):
    cfg, params = qwen
    eng = ServingEngine(cfg, params, batch=2, window_max=4, max_len=32,
                        eps_key=EPS_KEY, block_size=4, adaptive=False)
    cases = [
        (Request(uid=0, prompt=np.zeros(0, np.int64), new_tokens=4),
         "empty_prompt"),
        (Request(uid=1, prompt=np.asarray([1, 2]), new_tokens=0),
         "bad_new_tokens"),
        (Request(uid=2, prompt=np.asarray([1, 2]), new_tokens=10_000),
         "too_long"),
        (Request(uid=3, prompt=np.asarray([1, cfg.vocab]), new_tokens=4),
         "token_out_of_range"),
        (Request(uid=4, prompt=np.asarray([-1, 2]), new_tokens=4),
         "token_out_of_range"),
    ]
    for req, code in cases:
        assert eng.submit(req) is False
        assert req.error.code == code and not req.ok, (req.uid, req.error)
    assert len(eng.queue) == 0         # nothing malformed was admitted
    done = eng.run()
    assert {r.uid for r in done} == {0, 1, 2, 3, 4}
    assert eng.export_metrics()["requests_rejected"] == 5


# -- interleaved chaos schedules (satellite) ---------------------------------

CHAOS_RATES = {"arena_corrupt": 0.25, "arena_put": 0.25, "stage_drop": 0.25}


def _chaos_schedule(cfg, params, plan, batch=2, max_len=64):
    """Drive an engine through an arbitrary submit/step/preempt/migrate/
    cancel interleaving under exactness-preserving fault rates, then check
    every non-cancelled request against its solo run."""
    eng = ServingEngine(cfg, params, batch=batch, window_max=4,
                        max_len=max_len, eps_key=EPS_KEY, block_size=4,
                        adaptive=False, host_cache_mb=8,
                        # the first arena put always fails: how many tier
                        # ops a schedule makes depends on how many tokens
                        # each round accepts, and a rate alone may then
                        # inject no fault at all
                        faults=FaultPlan(schedule={"arena_put": (0,)},
                                         rates=CHAOS_RATES, seed=11))
    uid = 0
    for op, arg in plan:
        if op == "submit":
            L_p, new = arg
            rng = np.random.default_rng(100 + uid)
            eng.submit(Request(uid=uid,
                               prompt=rng.integers(0, cfg.vocab, L_p),
                               new_tokens=new))
            uid += 1
        elif op == "step":
            if eng.queue or any(s is not None for s in eng.slots):
                eng.step()
        elif op == "preempt":
            occ = [b for b in range(batch) if eng.slots[b] is not None]
            if occ:
                eng.preempt_slot(occ[arg % len(occ)])
        elif op == "migrate":
            occ = [b for b in range(batch) if eng.slots[b] is not None]
            free = [b for b in range(batch) if eng.slots[b] is None]
            if occ and free:
                eng.migrate_slot(occ[arg % len(occ)],
                                 free[arg % len(free)])
        elif op == "cancel":
            live = [r.uid for r in eng.queue.requests()] + [
                s.uid for s in eng.slots if s is not None]
            if live:
                eng.cancel(live[arg % len(live)])
    done = eng.run()
    assert len(done) == uid            # every submission is accounted for
    cancelled = [r for r in done if r.error is not None]
    assert all(r.error.code == "cancelled" for r in cancelled)
    assert len(cancelled) == eng.metrics.requests_cancelled
    for req in done:
        if req.error is None:
            np.testing.assert_array_equal(
                req.result,
                _solo(cfg, params, req, 4, max_len),
                err_msg=f"request {req.uid} diverged under chaos schedule")
    # every slot left fully clean
    assert np.asarray(eng.seq_ids).tolist() == [0] * batch
    assert np.asarray(eng.n).tolist() == [1] * batch
    return eng


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "gemma3-1b",
                                  "deepseek-v3-671b",
                                  "jamba-1.5-large-398b"])
def test_interleaved_cancel_fault_preempt_migrate_exact(arch):
    """Deterministic chaos interleavings across the mixer zoo: cancels,
    parks, slot moves, and seeded fault rates on every host-tier seam —
    survivors stay bitwise equal to solo runs."""
    cfg = get_config(arch, reduced=True)
    params = TransformerLM.init(jax.random.PRNGKey(0), cfg)
    # uid 0 wants 40 tokens: one rounds_per_sync dispatch cannot finish it,
    # so the first preempt always finds it running (every arch)
    plan = [("submit", (3, 40)), ("submit", (5, 6)), ("step", None),
            ("preempt", 0), ("submit", (2, 10)), ("step", None),
            ("cancel", 1), ("migrate", 1), ("step", None),
            ("submit", (7, 5)), ("preempt", 1), ("cancel", 0),
            ("step", None), ("migrate", 0), ("submit", (4, 6))]
    eng = _chaos_schedule(cfg, params, plan)
    assert eng.metrics.preemptions >= 1
    assert eng.metrics.requests_cancelled >= 1
    assert eng.faults.total_fired >= 1


def test_interleaved_chaos_schedules_hypothesis(qwen):
    """Property form: random interleavings of submit / step / preempt /
    migrate / cancel under seeded fault rates keep survivors solo-exact."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    cfg, params = qwen

    op = st.one_of(
        st.tuples(st.just("submit"),
                  st.tuples(st.integers(1, 8), st.integers(2, 8))),
        st.tuples(st.just("step"), st.none()),
        st.tuples(st.just("preempt"), st.integers(0, 3)),
        st.tuples(st.just("migrate"), st.integers(0, 3)),
        st.tuples(st.just("cancel"), st.integers(0, 3)),
    )

    @hyp.settings(max_examples=8, deadline=None,
                  suppress_health_check=list(hyp.HealthCheck))
    @hyp.given(st.lists(op, min_size=2, max_size=8))
    def run_plan(plan):
        if not any(p[0] == "submit" for p in plan):
            plan = [("submit", (2, 4))] + plan
        _chaos_schedule(cfg, params, plan)

    run_plan()
