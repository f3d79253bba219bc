"""Host spans of the engine's step phases (``SpanLog``) and the named scopes
of its device programs: both must reach a profile, the spans on the host
plane of the profiler's trace and the scopes in the programs' op metadata,
without renaming the programs."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import TransformerLM
from repro.serving import (Request, ServingEngine, SpanLog,
                           default_span_log)

EPS_KEY = jax.random.PRNGKey(9)
PHASES = ["serve.admit", "serve.round_dispatch", "serve.sync",
          "serve.harvest"]


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen3-1.7b", reduced=True)
    params = TransformerLM.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(qwen, **kw):
    cfg, params = qwen
    return ServingEngine(cfg, params, batch=2, window_max=4, max_len=48,
                         eps_key=EPS_KEY, block_size=4, adaptive=False, **kw)


def _requests(cfg, n=3):
    rng = np.random.default_rng(1)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab, size=9 + i),
                    new_tokens=5) for i in range(n)]


def _host_spans(trace_dir):
    """``(start_ns, end_ns, name)`` of every ``serve.*`` event on the host
    plane of the one profile under ``trace_dir``."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted((ev.start_ns, ev.end_ns, ev.name)
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("serve."))


def _records(eng):
    """The spans ``eng`` recorded into the process's span log."""
    return [r for r in default_span_log().records
            if r.engine == eng._engine_no]


def _inside(outer, inner):
    return outer.t0 <= inner.t0 <= inner.t1 <= outer.t1


def test_step_spans_reach_the_profile_and_the_log(qwen, tmp_path):
    cfg, _ = qwen
    eng = _engine(qwen)
    for r in _requests(cfg):
        eng.submit(r)
    eng.step()                       # compiles outside the profile
    with jax.profiler.trace(str(tmp_path)):
        eng.step()
        eng.step()

    # the profile: each serve.step holds the four phases, in order
    host = _host_spans(tmp_path)
    steps = [(s, e) for s, e, n in host if n == "serve.step"]
    assert len(steps) == 2
    for s0, s1 in steps:
        inside = [(s, e, n) for s, e, n in host
                  if s0 <= s and e <= s1 and n != "serve.step"]
        order = [n for _, _, n in inside if n in PHASES]
        assert order == PHASES, order
        ends = [e for _, e, n in inside if n in PHASES]
        starts = [s for s, _, n in inside if n in PHASES]
        assert all(e <= s for e, s in zip(ends, starts[1:]))

    # the log: the same spans, on increasing monotonic stamps, each inside
    # the span that was open around it
    recs = _records(eng)
    assert all(r.t0 <= r.t1 for r in recs)
    assert all(a.t1 <= b.t1 for a, b in zip(recs, recs[1:]))
    top = [r for r in recs if r.name == "serve.step"]
    assert [r.step for r in top] == [1, 2, 3]
    for st in top:
        kids = [r for r in recs if r.step == st.step and r is not st]
        assert all(_inside(st, r) for r in kids)
        phases = sorted((r for r in kids if r.name in PHASES),
                        key=lambda r: r.t0)
        assert [r.name for r in phases] == PHASES
        assert all(a.t1 <= b.t0 for a, b in zip(phases, phases[1:]))
    # the first step admitted both slots: request spans inside serve.admit
    admit = next(r for r in recs if r.name == "serve.admit" and r.step == 1)
    reqs = [r for r in recs if r.name == "serve.admit_request"]
    assert [r.uid for r in reqs[:2]] == [0, 1]
    assert all(_inside(admit, r) for r in reqs[:2])
    for name in ("serve.prefix_lookup", "serve.alloc_blocks",
                 "serve.slot_state", "serve.prefill_dispatch"):
        inner = [r for r in recs if r.name == name]
        assert len(inner) == len(reqs), name
        assert all(_inside(q, r) and q.uid == r.uid
                   for q, r in zip(reqs, inner)), name
    # the profile holds each span the log closed while it ran
    assert sum(n == "serve.sync" for _, _, n in host) == 2


class _Timed:
    """``fn`` with each call recorded as ``(label, t0, t1)`` in ``calls``;
    its other attributes (``lower``, ...) pass through."""

    def __init__(self, fn, label, calls):
        self.fn, self.label, self.calls = fn, label, calls

    def __call__(self, *a, **kw):
        t0 = time.monotonic()
        try:
            return self.fn(*a, **kw)
        finally:
            self.calls.append((self.label, t0, time.monotonic()))

    def __getattr__(self, name):
        return getattr(self.fn, name)


def _stamp(eng, name, calls, program=None):
    """Record each call of ``eng``'s method ``name`` in ``calls`` (for a
    method that returns a program, each call of that program, as
    ``program``)."""
    f = getattr(eng, name)
    if program is None:
        setattr(eng, name, _Timed(f, name, calls))
    else:
        setattr(eng, name, lambda *a: _Timed(f(*a), program, calls))


# the work each span encloses, which the span readers measure
ENCLOSED_BY = {
    "_poll_queue_deadlines": "serve.admit",
    "_reconcile_staging": "serve.admit",
    "_admit_pending": "serve.admit",
    "_stage_pending": "serve.admit",
    "_prefetch_queued": "serve.admit",
    "_admit": "serve.admit_request",
    "prefill program": "serve.prefill_dispatch",
    "_round_args": "serve.round_dispatch",
    "round program": "serve.round_dispatch",
    "_harvest": "serve.harvest",
}


def test_step_spans_enclose_their_phases(qwen):
    cfg, _ = qwen
    eng = _engine(qwen)
    calls = []
    for name in ENCLOSED_BY:
        if name.endswith(" program"):
            continue
        _stamp(eng, name, calls)
    _stamp(eng, "_prefill_fn", calls, program="prefill program")
    _stamp(eng, "_round_loop_fn", calls, program="round program")
    for r in _requests(cfg):
        eng.submit(r)
    for _ in range(3):
        eng.step()
    recs = _records(eng)
    assert {name for name, _, _ in calls} == set(ENCLOSED_BY)
    for name, t0, t1 in calls:
        assert any(r.name == ENCLOSED_BY[name] and r.t0 <= t0 <= t1 <= r.t1
                   for r in recs), name
    # the sync lies between the round program's call and the harvest
    for st in (r for r in recs if r.name == "serve.step"):
        sync = [r for r in recs if r.name == "serve.sync"
                and r.step == st.step]
        rnd = [t1 for n, t0, t1 in calls
               if n == "round program" and st.t0 <= t0 <= st.t1]
        hv = [t0 for n, t0, t1 in calls
              if n == "_harvest" and st.t0 <= t0 <= st.t1]
        assert len(sync) == len(rnd) == len(hv) == 1
        assert rnd[0] <= sync[0].t0 <= sync[0].t1 <= hv[0]
    # an evicted block's pull to the host tier is a serve.spill span
    blk = eng.owned[0][0] if eng.owned[0] else 0
    n0 = len(_records(eng))
    assert eng._make_spill_hook(0)(blk, ("spans", 0))
    assert [r.name for r in _records(eng)[n0:]] == ["serve.spill"]


def test_span_log_is_bounded_and_records_on_error():
    log = SpanLog(maxlen=4)
    for i in range(6):
        with log.span("serve.step", step=i):
            pass
    assert len(log.records) == 4
    assert [r.step for r in log.records] == [2, 3, 4, 5]
    t0, t1 = log.records[2].t0, log.records[3].t1
    assert [r.step for r in log.spans(t0, t1)] == [4, 5]
    with pytest.raises(ValueError):
        with log.span("serve.harvest", uid=7):
            raise ValueError("boom")
    assert log.records[-1].name == "serve.harvest"
    assert log.records[-1].uid == 7
    assert log.records[-1].t0 <= log.records[-1].t1


@pytest.mark.parametrize("staging_slots", [0, 1])
def test_programs_carry_named_scopes_and_keep_their_names(qwen,
                                                          staging_slots):
    cfg, params = qwen
    eng = _engine(qwen, staging_slots=staging_slots)
    low = eng._round_loop_fn(4, 2).lower(*eng._round_args())
    assert "module @jit_fn" in low.as_text()
    text = low.as_text(debug_info=True)
    # op locations: jit(fn)/serve.round_loop/while/body/verify_round/noise/..
    for scope in ("jit(fn)/serve.round_loop/", "/verify_round/",
                  "/verify_round/noise/"):
        assert scope in text, scope
    C = 8
    low = eng._prefill_fn(C).lower(
        params, eng.paged, jnp.zeros((1, eng.nb), jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.zeros((1, C), jnp.int32),
        jnp.zeros((1,), jnp.int32))
    assert "module @jit_fn" in low.as_text()
    assert "jit(fn)/serve.prefill/" in low.as_text(debug_info=True)
