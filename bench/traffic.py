"""One general, seeded generator for every traffic mix.

A mix is a JSON file ``bench/traffic/<mix>.json`` of parameters:

``loop``
    ``"closed"``: ``clients`` callers, each sending its next request as soon
    as its previous one has finished (or failed); ``"open"``: independent
    users arriving as a Poisson process at ``rate_per_s``.
``prompt`` / ``output``
    Length distributions, ``{"dist": "uniform" | "loguniform", "lo", "hi"}``
    or ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}`` (clipped).
``documents`` (optional)
    ``{"count": D, "length": <distribution>}``: D shared documents, prefilled
    during set-up; each request is one of them, chosen uniformly, followed by
    a fresh question whose length is ``prompt``.
``strata``
    Lengths and gaps are drawn stratified: every block of ``strata``
    consecutive draws takes one point, uniform within the slice, from each
    of the ``strata`` equal-probability slices of the distribution.
``pool``
    How many requests' lengths the mix draws (default 128), from the whole
    of each distribution. The lengths, their order, each request's choice
    of document and the documents' lengths are the same for every seed, so
    that every seed asks for the same work; the seed draws every token id
    and the open loop's arrival gaps. Request ``i`` (past the closed loop's
    first requests) has the lengths of pool entry ``i mod pool``. The
    program compiles some small operations once per distinct length:
    set-up compiles those of the pool's lengths, none compiles while the
    window is measured, and after a checkout's first run every program is
    in its compile cache.
``warmup_s``
    Seconds of this traffic served before the measured window opens.
``engine``
    ``ServingEngine`` settings of the cell (batch, window_max, block_size,
    max_len, and num_blocks where the default would not fit).
``check``
    How many served tokens (and at most how many requests) the reference
    compares after the window.

In a closed loop the clients' first requests are the pool's first
``clients`` entries with their outputs cut to ``(c + 1/2) / clients`` of
the drawn length (at least the distribution's ``lo``), in a fixed
order, so that completions spread out instead of arriving together.

The same seed gives the same documents, requests and arrival schedule.
Request ``i``'s token ids depend only on the seed and ``i``.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
EDGE = 1e-9          # keeps a quantile's u inside (0, 1)


def load_mix(name: str) -> dict:
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def quantile(dist: dict, u: float) -> float:
    """The ``u`` quantile of a length distribution (before rounding)."""
    kind, lo, hi = dist["dist"], dist["lo"], dist["hi"]
    if kind == "uniform":
        return lo + u * (hi - lo)
    if kind == "loguniform":
        return math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
        return min(max(x, lo), hi)
    raise ValueError(f"unknown distribution {kind!r}")


def length(dist: dict, u: float) -> int:
    """A whole length at quantile ``u``, within ``[lo, hi]``."""
    return min(max(int(round(quantile(dist, u))), dist["lo"]), dist["hi"])


class Stratified:
    """Quantiles ``u`` in blocks of ``k``: each block one uniform point in
    each slice ``[j/k, (j+1)/k)``, in a seeded order."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.buf = k, rng, []

    def next(self) -> float:
        if not self.buf:
            u = (self.rng.permutation(self.k) + self.rng.random(self.k)) \
                / self.k
            self.buf = list(np.clip(u, EDGE, 1.0 - EDGE))[::-1]
        return float(self.buf.pop())


@dataclass
class Spec:
    """One request as the traffic defines it."""
    index: int
    prompt: np.ndarray           # int32 token ids
    new_tokens: int
    doc: Optional[int] = None    # index of the shared document it starts with


class Traffic:
    """The request sequence, arrival gaps and documents of one mix under one
    seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, int(seed), vocab
        k = int(mix.get("strata", 16))

        def rng(stream: str, seed: int = self.seed):
            return np.random.default_rng(
                [seed % 2 ** 64] + [ord(c) for c in stream])

        def fixed(stream: str):         # the same draw for every seed
            return rng(stream, 0)

        self.documents: list[np.ndarray] = []
        docs = mix.get("documents")
        n_docs = docs["count"] if docs else 0
        if docs:
            u = Stratified(n_docs, fixed("doc-lengths"))
            r = rng("doc-tokens")
            self.documents = [
                r.integers(0, vocab, length(docs["length"], u.next()),
                           dtype=np.int32) for _ in range(n_docs)]
        pool = int(mix.get("pool", 128))
        up, uo = Stratified(k, fixed("prompt")), Stratified(k, fixed("output"))
        pick = fixed("doc-choice")
        docs_of = []
        while len(docs_of) < pool:
            docs_of += list(pick.permutation(max(n_docs, 1)))
        entries = [(length(mix["prompt"], up.next()),
                    length(mix["output"], uo.next()),
                    int(docs_of[j]) if docs else None) for j in range(pool)]
        self.clients = int(mix["clients"]) if mix["loop"] == "closed" else 0
        first = []
        for c in range(self.clients):
            q, o, d = entries[c % pool]
            first.append((q, max(mix["output"]["lo"],
                                 int(o * (c + 0.5) / self.clients)), d))
        order = fixed("order")
        self.first = [first[j] for j in order.permutation(len(first))]
        self.entries = [entries[j] for j in order.permutation(pool)]
        if mix["loop"] == "open":
            self._gap = Stratified(k, rng("gaps"))
            self.rate = float(mix["rate_per_s"])
        self._count = 0

    # -- the request sequence ------------------------------------------------
    def lengths(self, i: int) -> tuple[int, int, Optional[int]]:
        """Request ``i``'s question length, output length and document."""
        if i < self.clients:
            return self.first[i]
        return self.entries[(i - self.clients) % len(self.entries)]

    def next_spec(self) -> Spec:
        """The next request."""
        i = self._count
        self._count += 1
        q_len, new, d = self.lengths(i)
        r = np.random.default_rng([self.seed % 2 ** 64, 7, i])
        q = r.integers(0, self.vocab, q_len, dtype=np.int32)
        if d is not None:
            return Spec(i, np.concatenate([self.documents[d], q]), new, d)
        return Spec(i, q, new)

    def next_gap(self) -> float:
        """Seconds from one open-loop arrival to the next."""
        return -math.log(1.0 - self._gap.next()) / self.rate

    # -- what the program will see -----------------------------------------
    def _shapes(self):
        for q, o, d in self.first + self.entries:
            p = q + (len(self.documents[d]) if d is not None else 0)
            yield p, p + o

    def prompt_lengths(self) -> list[int]:
        """Every prompt length this seed's traffic sends."""
        return sorted({p for p, _ in self._shapes()})

    def final_lengths(self) -> list[int]:
        """Every prompt + output length this seed's traffic sends."""
        return sorted({f for _, f in self._shapes()})
