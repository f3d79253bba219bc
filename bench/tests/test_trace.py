"""The trace reduction: operation names, self times of nested device
operations, busy time, programs classified by the harness's per-step counts
(a count that disagrees is noticed), kernel time and the breakdown, on a
synthetic trace and on one recorded on a TPU v5e."""
import json
import re
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def test_op_names_drop_percent_and_numeric_suffix():
    assert trace.op_name("%copy.72 = bf16[28,3299] copy(...)") == "copy"
    assert trace.op_name("%paged_decode_kernel.3 = (bf16[1])") == \
        "paged_decode_kernel"
    assert trace.op_name("%while = (s32[]) while(...)") == "while"
    assert trace.op_name("fusion.v2") == "fusion.v2"


def test_self_times_of_nested_events():
    # a loop [0, 10) holding two ops, one of which holds another
    ev = [(0, 10, "while"), (1, 4, "fusion"), (5, 9, "call"),
          (6, 8, "kernel")]
    own = dict(trace._self_times(ev))
    assert own == {"while": 3, "fusion": 3, "call": 2, "kernel": 2}
    assert sum(own.values()) == 10


def test_union_merges_overlaps():
    total, merged = trace._union([(0, 3), (2, 5), (7, 8)])
    assert total == 6 and merged == [[0, 5], [7, 8]]


def _plane(pid, name, lines):
    meta, body = {}, []
    for lid, (lname, evs) in enumerate(lines, 1):
        items = " ".join(
            f"events {{ metadata_id: {meta.setdefault(n, len(meta) + 1)} "
            f"offset_ps: {s * 10 ** 9} duration_ps: {(e - s) * 10 ** 9} }}"
            for s, e, n in evs)
        body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                    f"{items} }}")
    md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                  f'"{n}" }} }}' for n, i in meta.items())
    return f'planes {{ id: {pid} name: "{name}" {" ".join(body)} {md} }}'


# times in ms: window [0, 100); step 1 [10, 50) runs a prefill then the
# round loop, step 2 [60, 90) the round loop alone
HOST = [(0, 100, "bench.window"), (5, 9, "bench.submit"),
        (10, 50, "bench.step"), (60, 90, "bench.step")]
MODULES = [(12, 20, "jit_fn(11)"), (22, 48, "jit_fn(22)"),
           (62, 88, "jit_fn(22)"), (50, 51, "jit_scatter(7)")]
OPS = [(12, 20, "%while = (bf16[1,64,8]) while()"),
       (13, 15, "%paged_decode_kernel.1 = (bf16[1])"),
       (22, 48, "%while.4 = (s32[4,64]) while()"),
       (24, 30, "%paged_decode_kernel.2 = (bf16[4])"),
       (50, 51, "%scatter.1 = s32[4] scatter()"),
       (62, 88, "%while.4 = (s32[4,64]) while()"),
       (70, 80, "%paged_decode_kernel.2 = (bf16[4])")]


def synthetic(modules=MODULES):
    import jax
    text = _plane(1, "/host:CPU", [("python", HOST)]) + _plane(
        2, "/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", OPS)])
    return jax.profiler.ProfileData.from_text_proto(text)


def test_programs_classified_by_the_harness_counts():
    s = trace.reduce_trace(synthetic(), counts=[(1, 4), (0, 4)])
    assert s.classified, s.notes
    assert s.window_s == pytest.approx(0.1)
    assert s.programs == pytest.approx({"prefill": 0.008, "round": 0.052,
                                        "other": 0.001})
    assert s.kernels == pytest.approx({"paged_attention": 0.018})
    assert s.busy_s == pytest.approx(0.061)
    gaps = dict(s.idle_gaps)
    # each gap goes to the host span at its midpoint
    assert gaps == pytest.approx({"bench.submit": 0.012, "bench.step": 0.004,
                                  "none": 0.023})
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.061)
    ops = dict(s.device_ops)
    assert ops["paged_decode_kernel"] == pytest.approx(0.018)
    assert ops["while"] == pytest.approx(0.060 - 0.018)


@pytest.mark.parametrize("counts, classified", [
    # a prefill the harness did not count: step 1 does not vote, and the
    # prefill program is then covered by no vote
    ([(0, 4), (0, 4)], False),
    # a round loop in a step that ran no rounds: that step does not vote,
    # and its program is classified by step 1's vote on its fingerprint
    ([(1, 4), (0, 0)], True),
    (None, False),              # no counts from the harness
    ([(1, 4)], False),          # fewer steps than the trace holds
])
def test_a_count_that_disagrees_is_noticed(counts, classified):
    s = trace.reduce_trace(synthetic(), counts=counts)
    assert s.classified == classified and s.notes
    if classified:
        assert s.programs == pytest.approx({"prefill": 0.008,
                                            "round": 0.052, "other": 0.001})


def test_a_program_voted_both_ways_is_not_classified():
    mods = [(12, 20, "jit_fn(22)"), (22, 48, "jit_fn(22)"),
            (62, 88, "jit_fn(22)")]
    s = trace.reduce_trace(synthetic(mods), counts=[(1, 4), (0, 4)])
    assert not s.classified
    assert s.programs == pytest.approx({"unknown": 0.060})


def test_cut_to_text_proto_reduces_alike():
    import jax
    pd = synthetic()
    text = trace.to_text_proto(pd, 1, 1)
    s = trace.reduce_trace(jax.profiler.ProfileData.from_text_proto(text),
                           counts=[(0, 4)])
    assert s.classified and s.window_s == pytest.approx(0.030)
    assert s.programs == pytest.approx({"round": 0.026})
    assert s.kernels == pytest.approx({"paged_attention": 0.010})


# a layer scan's loop state: its counter, then the rows' hidden states
LAYER_SCAN = r"= \(s32\[\]\{[^}]*\}, bf16\[(\d+),\d+,2048\]"


def _classes_by_content(path):
    """Independent of the reduction: each engine program's class as its
    operations say. A prefill carries one row of C tokens through its
    layer scan (a ``while`` over ``bf16[1,C,2048]``), the round loop the
    batch's 16 rows (``bf16[16,W,2048]``). Seconds by class."""
    pd = trace.load(path)
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    lines = {ln.name: ln for ln in dev.lines}
    loops = [(e.start_ns, e.name) for e in lines["XLA Ops"].events
             if trace.op_name(e.name).startswith("while")]
    out = {}
    for m in lines["XLA Modules"].events:
        if not m.name.startswith("jit_fn("):
            continue
        rows = {int(r) for t, op in loops if m.start_ns <= t <= m.end_ns
                for r in re.findall(LAYER_SCAN, op)}
        cls = {frozenset({1}): "prefill", frozenset({16}): "round"}.get(
            frozenset(rows), "?")
        out[cls] = out.get(cls, 0.0) + (m.end_ns - m.start_ns) * 1e-9
    return out


def test_recorded_docqa_steps_classified_as_their_operations_say():
    """Two steps of ``qwen3-1.7b.docqa-8k`` recorded on a TPU v5e (seed
    4100000002) and cut by ``to_text_proto``: 14 prefill chunks and two
    round loops of 4 rounds."""
    meta = json.loads((DATA / "docqa-steps.json").read_text())
    path = DATA / "docqa-steps.textproto.gz"
    s = trace.reduce_trace(path, [tuple(c) for c in meta["counts"]])
    assert s.classified and not s.notes, s.notes
    truth = _classes_by_content(path)
    assert set(truth) == {"prefill", "round"}
    assert s.programs["prefill"] == pytest.approx(truth["prefill"])
    assert s.programs["round"] == pytest.approx(truth["round"])
    # the kernel runs inside both, and no longer than they do
    assert 0 < s.kernels["paged_attention"] < truth["prefill"] + truth["round"]
    assert s.device_ops[0][0] == "paged_decode_kernel"
    assert 0 < s.busy_s <= s.window_s
    # counts that disagree with the recording are noticed
    bad = trace.reduce_trace(path, [(0, 4), (0, 4)])
    assert not bad.classified and bad.notes
