"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, device time by program and by kernel, the
operations that took most time, and the device's idle gaps by what the host
was doing.

Read with ``jax.profiler.ProfileData`` alone. Device planes are those named
``/device:TPU:<n>``; on each, the line of executed programs (``XLA Modules``)
and of operations (``XLA Ops``) are used. The host's spans are the harness's
own ``TraceAnnotation``s (``bench.*``) on the host plane.

Programs (``XLA Modules`` events, named ``jit_<function>(<fingerprint>)``)
are classified by fingerprint, from what the harness counted in each step:
the engine's round loop and prefill are both ``jit_fn`` programs today, so
their names do not tell them apart. A ``bench.step`` span in which the
harness saw ``p`` prefill chunks and a device loop of verify rounds holds
``p + 1`` engine programs, the round loop last (admission and its prefills
run first, then one round-loop dispatch, then the sync); one with no rounds
holds ``p`` prefills. Only steps whose count of engine programs agrees
vote; every program is then classified by the vote on its fingerprint.
Steps whose count disagrees are counted in the notes. A fingerprint voted
both ways, or an engine program no vote covers, is named there too, and
``classified`` is then False: the readers of program and kernel times
report nothing.

Operations (``XLA Ops`` events) nest: a ``while`` loop's event spans the
operations of its body. Busy time is the union of all of them; the
breakdown's operation times are self times (an event's time less that of
the events inside it), summed by operation name without its ``%`` and
numeric suffix. The paged-attention kernel is the operation the jitted
wrapper names ``paged_decode_kernel`` (``paged_latent_kernel`` for MLA).
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
from collections import defaultdict
from dataclasses import dataclass, field

ENGINE_MODULE = "jit_fn"
DEVICE_PREFIX = "/device:TPU:"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # averaged over the chips used
    programs: dict = field(default_factory=dict)   # class -> seconds
    kernels: dict = field(default_factory=dict)    # kernel -> seconds
    device_ops: list = field(default_factory=list)  # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)   # [[host span, seconds]]
    modules: dict = field(default_factory=dict)    # module name -> seconds
    classified: bool = True             # every engine program has a class
    notes: list = field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(src):
    """A ``ProfileData`` from an ``.xplane.pb`` path, a ``.textproto``
    (or ``.textproto.gz``) path, or a ``ProfileData``."""
    import jax
    if not isinstance(src, (str, os.PathLike)):
        return src
    if str(src).endswith((".textproto", ".textproto.gz")):
        with (gzip.open if str(src).endswith(".gz") else open)(
                src, "rt") as f:
            return jax.profiler.ProfileData.from_text_proto(f.read())
    return jax.profiler.ProfileData.from_file(str(src))


def _union(intervals):
    """Total length and merged list of [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


KERNELS = {"paged_decode_kernel": "paged_attention",
           "paged_latent_kernel": "paged_attention"}


def op_name(name: str) -> str:
    """``%copy.72 = bf16[...] copy(...)`` -> ``copy``."""
    base = name.split(" = ", 1)[0].lstrip("%")
    head, _, tail = base.rpartition(".")
    return head if head and tail.isdigit() else base


def _self_times(events):
    """Self time of each of nested ``(start, end, name)`` events."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    own = [e - s for s, e, _ in events]
    stack = []                                   # indices of open events
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][2], own[i]) for i in range(len(events))]


def classify(mod_evs, steps, counts, notes):
    """Class (``round``, ``prefill``, ``other`` or ``unknown``) of each
    module event ``(start, end, name)``, and whether every engine program
    was classified, by one class; ``steps`` are the
    ``bench.step`` spans and ``counts`` the harness's ``(prefill chunks,
    rounds)`` of each. See the module's docstring."""
    votes = defaultdict(set)
    per_step = defaultdict(list)
    starts = [s for s, _ in steps]
    for ev in mod_evs:
        if ev[2].split("(")[0] == ENGINE_MODULE:
            i = bisect.bisect_right(starts, ev[0]) - 1
            if i >= 0 and ev[0] <= steps[i][1]:
                per_step[i].append(ev)
    if counts is None or len(counts) != len(steps):
        notes.append(f"harness logged {None if counts is None else len(counts)}"
                     f" steps, the trace holds {len(steps)}: programs not "
                     "classified")
        counts = None
    bad_steps = 0
    for i, evs in per_step.items():
        if counts is None:
            break
        prefills, rounds = counts[i]
        if len(evs) != prefills + (rounds > 0):
            bad_steps += 1
            continue
        evs.sort()
        for j, ev in enumerate(evs):
            votes[ev[2]].add("round" if rounds > 0 and j == len(evs) - 1
                             else "prefill")
    if bad_steps:
        notes.append(f"{bad_steps} of {len(per_step)} steps hold another "
                     "count of engine programs than the harness counted")
    cls = {}
    for ev in mod_evs:
        if ev[2].split("(")[0] != ENGINE_MODULE:
            cls[ev] = "other"
        elif len(votes.get(ev[2], ())) == 1:
            cls[ev] = next(iter(votes[ev[2]]))
        else:
            cls[ev] = "unknown"
    both = [name for name, v in votes.items() if len(v) > 1]
    if both:
        notes.append(f"classified as both round and prefill: {both}")
    unknown = sum(c == "unknown" for c in cls.values())
    if unknown:
        notes.append(f"{unknown} engine programs not classified")
    return cls, counts is not None and not both and not unknown


def reduce_trace(src, counts=None, window_span: str = "bench.window",
                 step_span: str = "bench.step") -> TraceSummary:
    """Reduce a trace (see :func:`load`); ``counts`` lists, for each
    ``bench.step`` inside the window in order, the harness's
    ``(prefill chunks, verify rounds)``."""
    pd = load(src)
    host_spans = []                     # (start, end, name) of bench.* spans
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.start_ns, ev.end_ns, ev.name))
    win = [(s, e) for s, e, n in host_spans if n == window_span]
    if not win:
        raise ValueError(f"no {window_span!r} span in the trace")
    w0, w1 = win[0]
    steps = sorted((s, e) for s, e, n in host_spans
                   if n == step_span and w0 <= s < w1)
    # the harness's spans inside the window do not overlap one another
    inner = sorted((s, e, n) for s, e, n in host_spans if n != window_span)
    inner_starts = [s for s, _, _ in inner]
    summary = TraceSummary(window_s=(w1 - w0) * 1e-9, busy_s=0.0)
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}<n> plane in the trace")

    op_time = defaultdict(float)
    programs = defaultdict(float)
    modules = defaultdict(float)
    kernel_s = defaultdict(float)
    busy_total = 0.0
    gaps_by_span = defaultdict(float)
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get("XLA Ops")
        mods = lines.get("XLA Modules")
        if ops is None or mods is None:
            summary.notes.append(f"{plane.name}: lines {sorted(lines)}")
            summary.classified = False
            continue
        mod_evs = [(ev.start_ns, ev.end_ns, ev.name) for ev in mods.events
                   if ev.end_ns > w0 and ev.start_ns < w1]
        cls_of, ok = classify(mod_evs, steps, counts, summary.notes)
        summary.classified &= ok
        spans = []
        for ev in mod_evs:
            s, e, name = ev
            t = (min(e, w1) - max(s, w0)) * 1e-9
            modules[name.split("(")[0].strip()] += t
            programs[cls_of[ev]] += t
            spans.append((s, e, cls_of[ev]))
        spans.sort()
        starts = [s for s, _, _ in spans]

        intervals, kernel_spans = [], []
        for ev in ops.events:
            s, e = ev.start_ns, ev.end_ns
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            name = op_name(ev.name)
            intervals.append((s, e, name))
            if name in KERNELS:
                kernel_spans.append((s, e, KERNELS[name]))
        for name, t in _self_times(intervals):
            op_time[name] += t * 1e-9
        for s, e, k in kernel_spans:
            j = bisect.bisect_right(starts, s) - 1
            if j >= 0 and spans[j][0] <= s <= spans[j][1] and \
                    spans[j][2] in ("round", "prefill"):
                kernel_s[k] += (e - s) * 1e-9
        intervals = [(s, e) for s, e, _ in intervals]
        busy, merged = _union(intervals)
        busy_total += busy * 1e-9
        # idle gaps inside the window, by the innermost host span at the
        # gap's midpoint
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            k = bisect.bisect_right(inner_starts, mid) - 1
            name = inner[k][2] if k >= 0 and inner[k][1] >= mid else "none"
            gaps_by_span[name] += (b - a) * 1e-9

    n = max(1, len(devices))
    summary.busy_s = busy_total / n
    summary.programs = {k: v / n for k, v in programs.items()}
    summary.modules = {k: v / n for k, v in modules.items()}
    summary.kernels = {k: v / n for k, v in kernel_s.items()}
    summary.device_ops = sorted(([k, v / n] for k, v in op_time.items()),
                                key=lambda kv: -kv[1])[:10]
    summary.idle_gaps = sorted(([k, v / n] for k, v in gaps_by_span.items()),
                               key=lambda kv: -kv[1])[:10]
    return summary


def _quote(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"').replace(
        "\n", "\\n") + '"'


def to_text_proto(src, first_step: int, n_steps: int) -> str:
    """The part of a trace that the reduction reads, cut to ``n_steps``
    ``bench.step`` spans from the window's ``first_step``: an XSpace text
    proto holding the ``bench.*`` host spans and the device planes' program
    and operation events in that stretch. This makes the recorded trace the
    tests keep (``python3 -m bench.trace <xplane.pb> <first> <n> > out``)."""
    pd = load(src)
    host = [(ev.start_ns, ev.end_ns, ev.name) for plane in pd.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for ev in line.events if ev.name.startswith("bench.")]
    w0, w1 = next((s, e) for s, e, n in host if n == "bench.window")
    steps = sorted((s, e) for s, e, n in host
                   if n == "bench.step" and w0 <= s < w1)
    t0, t1 = steps[first_step][0], steps[first_step + n_steps - 1][1]
    out = []

    def plane(pid, name, lines):
        names = {}
        body = []
        for lid, (lname, evs) in enumerate(lines, 1):
            items = []
            for s, e, n in evs:
                mid = names.setdefault(n, len(names) + 1)
                items.append(f"events {{ metadata_id: {mid} "
                             f"offset_ps: {round((s - t0) * 1000)} "
                             f"duration_ps: {round((e - s) * 1000)} }}")
            body.append(f"  lines {{ id: {lid} name: {_quote(lname)} "
                        f"timestamp_ns: {int(t0)}\n    "
                        + "\n    ".join(items) + "\n  }")
        meta = [f"  event_metadata {{ key: {i} value {{ id: {i} name: "
                f"{_quote(n)} }} }}" for n, i in names.items()]
        out.append(f"planes {{ id: {pid} name: {_quote(name)}\n"
                   + "\n".join(body + meta) + "\n}")

    # the window span is cut to the kept stretch
    plane(1, "/host:CPU", [("bench", [(t0, t1, "bench.window")] + [
        (s, e, n) for s, e, n in host
        if n != "bench.window" and s >= t0 and e <= t1])])
    for p in pd.planes:
        if p.name.startswith(DEVICE_PREFIX) and \
                p.name[len(DEVICE_PREFIX):].isdigit():
            plane(len(out) + 1, p.name, [
                (ln.name, [(ev.start_ns, ev.end_ns, ev.name)
                           for ev in ln.events
                           if ev.start_ns >= t0 and ev.end_ns <= t1])
                for ln in p.lines if ln.name in ("XLA Modules", "XLA Ops")])
    return "\n".join(out) + "\n"


if __name__ == "__main__":
    import sys
    sys.stdout.write(to_text_proto(sys.argv[1], int(sys.argv[2]),
                                   int(sys.argv[3])))
