"""Jaxpr / post-SPMD HLO parsing backend of the contract engine
(DESIGN.md §17; no jax side effects on import).

This is the measurement layer the declarative rules in
:mod:`repro.analysis.rules` are built on: text parsing of compiled HLO
(collective ops — including their *async* lowered forms — and dtype-sized
result shapes) and structural walks of ClosedJaxprs (primitive census with
recursion into ``while``/``scan``/``jit``/pallas sub-jaxprs, with rank
filtering and per-equation evidence). It subsumes the former
``repro.launch.hlo_analysis`` module, which survives as a thin re-export
shim for external callers; everything in-repo goes through
``repro.analysis``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

# Collective op spellings in post-SPMD HLO. The sync forms are how a
# single-stream lowering spells them; the ``-start`` forms are the async
# lowering (``--xla_..._enable_async_collectives`` and TPU/GPU defaults)
# where the op is split into start/done pairs — an async-lowered program
# used to slip past the zero-collective gate entirely (the PR 10 fix).
# Only the ``-start`` half is counted (the ``-done`` op consumes the
# handle and moves no new bytes); longer names must sort before their
# prefixes so ``all-reduce-start(`` is never misread as ``all-reduce(``.
_SYNC_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute")
_ASYNC_COLLECTIVES = ("all-reduce-start", "all-gather-start",
                      "collective-permute-start")
_COLLECTIVES = tuple(sorted(_SYNC_COLLECTIVES + _ASYNC_COLLECTIVES,
                            key=len, reverse=True))

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def parse_shape_bytes(type_text: str) -> int:
    """Sum the byte sizes of every ``dtype[dims]`` shape in ``type_text``
    (tuple result types contribute each element)."""
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(type_text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DTYPE_BYTES[dt]
    return nbytes


def find_collectives(hlo_text: str) -> list[dict]:
    """Every collective op in (post-SPMD) HLO text, with evidence: one
    record ``{op, line_no, line, bytes}`` per occurrence. Async-lowered
    start ops count like their sync forms (the regression the
    zero-collective gate needs); ``-done`` ops are skipped."""
    found = []
    for i, line in enumerate(hlo_text.splitlines(), start=1):
        stripped = line.strip()
        for coll in _COLLECTIVES:
            marker = f" {coll}("
            if marker not in stripped:
                continue
            # result type(s) appear between '=' and the op name
            lhs = stripped.split(marker)[0]
            if "=" not in lhs:
                continue
            type_part = lhs.split("=", 1)[1]
            found.append({"op": coll, "line_no": i,
                          "line": stripped[:200],
                          "bytes": parse_shape_bytes(type_part)})
            break
    return found


def parse_collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op in (post-SPMD) HLO,
    keyed by *base* op name: async start forms fold into their sync
    spelling (``all-reduce-start`` counts as ``all-reduce``), so the
    zero-collective gate ``all(count == 0)`` covers both lowerings."""
    totals = {c: {"bytes": 0, "count": 0} for c in _SYNC_COLLECTIVES}
    for rec in find_collectives(hlo_text):
        base = rec["op"]
        if base.endswith("-start"):
            base = base[:-len("-start")]
        totals[base]["bytes"] += rec["bytes"]
        totals[base]["count"] += 1
    return totals


@dataclass
class EqnSite:
    """One matched equation inside a (possibly nested) jaxpr."""
    primitive: str
    rank: int                      # max output rank
    path: str                      # e.g. "while/body/jit"
    eqn: str = field(repr=False, default="")   # pretty-printed, truncated
    shape: tuple = ()              # shape of the max-rank output

    def __str__(self):
        where = self.path or "<top>"
        return f"{self.primitive} (rank {self.rank}) at {where}: {self.eqn}"


def find_jaxpr_primitives(closed_jaxpr, names, min_rank: int = 0
                          ) -> list[EqnSite]:
    """Every equation matching ``names`` (and the rank filter) in a
    ClosedJaxpr, recursing into sub-jaxprs (scan/while/jit/pallas
    bodies). Returns :class:`EqnSite` evidence records — the structured
    counterpart of :func:`count_jaxpr_primitives`, used by contract
    Reports to *name* the offending equation instead of just counting."""
    names = frozenset(names)
    sites: list[EqnSite] = []

    def visit(jaxpr, path):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in names:
                shapes = [tuple(getattr(v.aval, "shape", ()))
                          for v in eqn.outvars]
                shape = max(shapes, key=len, default=())
                if len(shape) >= min_rank:
                    txt = str(eqn)
                    if len(txt) > 160:
                        txt = txt[:157] + "..."
                    sites.append(EqnSite(prim, len(shape), path, txt,
                                         shape))
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    sub_path = f"{path}/{eqn.primitive.name}" if path \
                        else eqn.primitive.name
                    visit(sub, sub_path)
    visit(getattr(closed_jaxpr, "jaxpr", closed_jaxpr), "")
    return sites


def count_jaxpr_primitives(closed_jaxpr, names, min_rank: int = 0):
    """Count primitive occurrences (by name) in a ClosedJaxpr, recursing
    into sub-jaxprs (scan/while/jit/pallas bodies). ``min_rank`` filters to
    equations whose first output has at least that many dims — e.g.
    ``count_jaxpr_primitives(jaxpr, ("scatter",), min_rank=3)`` counts
    pool-shaped scatters (the standalone window-writeback the fused kernel
    epilogue eliminates) while ignoring small per-row bookkeeping updates.

    The fused-round acceptance gate (DESIGN.md §11): a verify round's jaxpr
    must contain ZERO pool-ranked scatter eqns — every physical-pool write
    happens inside a pallas_call as an aliased epilogue."""
    counts = {n: 0 for n in names}
    for site in find_jaxpr_primitives(closed_jaxpr, names, min_rank):
        counts[site.primitive] += 1
    return counts


def find_dtype_leaks(closed_jaxpr, dtypes=("float64", "complex128")
                     ) -> list[EqnSite]:
    """Equations producing outputs of any of ``dtypes`` (recursive) —
    the :class:`~repro.analysis.rules.NoF64Leaks` evidence walk. A stray
    f64 on the hot path silently doubles bandwidth (and diverges from the
    bf16/f32 bit-exactness story), so it is a contract violation, not a
    style nit."""
    wanted = frozenset(dtypes)
    sites: list[EqnSite] = []

    def visit(jaxpr, path):
        for eqn in jaxpr.eqns:
            hits = [v for v in eqn.outvars
                    if str(getattr(v.aval, "dtype", "")) in wanted]
            if hits:
                rank = max(len(getattr(v.aval, "shape", ()))
                           for v in hits)
                txt = str(eqn)
                if len(txt) > 160:
                    txt = txt[:157] + "..."
                sites.append(EqnSite(eqn.primitive.name, rank, path, txt))
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    sub_path = f"{path}/{eqn.primitive.name}" if path \
                        else eqn.primitive.name
                    visit(sub, sub_path)
    visit(getattr(closed_jaxpr, "jaxpr", closed_jaxpr), "")
    return sites


def _sub_jaxprs(value):
    """Yield any jaxprs nested inside an eqn param value."""
    import jax.extend.core as jex_core  # deferred: no import side effects

    vals = value if isinstance(value, (list, tuple)) else [value]
    for v in vals:
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v
