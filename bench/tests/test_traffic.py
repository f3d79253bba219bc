"""The traffic generator: one seed, one schedule; lengths in range, drawn
from the whole distribution, stratified, the same for every seed."""
import numpy as np
import pytest

from bench.traffic import Stratified, Traffic, length, load_mix

MIXES = ["docqa-8k", "chat-poisson"]


def _draw(mix, seed, n=40):
    t = Traffic(mix, seed, vocab=1000)
    specs = [t.next_spec() for _ in range(n)]
    gaps = [t.next_gap() for _ in range(n)] if mix["loop"] == "open" else []
    return t, specs, gaps


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    mix = load_mix(name)
    a, sa, ga = _draw(mix, 2 ** 31 + 5)
    b, sb, gb = _draw(mix, 2 ** 31 + 5)
    c, sc, _ = _draw(mix, 2 ** 31 + 6)
    assert ga == gb
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.new_tokens == y.new_tokens for x, y in zip(sa, sb))
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(sa, sc))
    assert all(np.array_equal(x, y) for x, y in zip(a.documents, b.documents))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_in_range_and_continuous(name):
    mix = load_mix(name)
    k = mix["strata"]
    t, specs, gaps = _draw(mix, 7, n=4 * k)
    q_lens = [len(s.prompt) - (len(t.documents[s.doc]) if t.documents
                               else 0) for s in specs]
    outs = [s.new_tokens for s in specs]
    p, o = mix["prompt"], mix["output"]
    assert all(p["lo"] <= x <= p["hi"] for x in q_lens)
    assert all(o["lo"] <= x <= o["hi"] for x in outs)
    # lengths come from the whole range, not from a grid of k values
    assert len(set(q_lens)) > k and len(set(outs[t.clients:])) > k
    if t.documents:
        d = mix["documents"]["length"]
        assert all(d["lo"] <= len(x) <= d["hi"] for x in t.documents)
        assert len(t.documents) == mix["documents"]["count"]
        assert len({len(x) for x in t.documents}) == len(t.documents)
    if gaps:
        assert abs(np.mean(gaps) - 1.0 / mix["rate_per_s"]) \
            < 0.15 / mix["rate_per_s"]
    # the shapes set-up warms are those the traffic sends
    assert set(len(s.prompt) for s in specs) <= set(t.prompt_lengths())
    assert set(len(s.prompt) + s.new_tokens for s in specs) <= set(
        t.final_lengths())


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_same_sizes_in_the_same_order(name):
    mix = load_mix(name)
    a, sa, _ = _draw(mix, 11, n=200)
    b, sb, _ = _draw(mix, 2 ** 32 + 12, n=200)
    assert a.prompt_lengths() == b.prompt_lengths()
    assert a.final_lengths() == b.final_lengths()
    assert a.first == b.first and a.entries == b.entries
    assert [(len(x.prompt), x.new_tokens, x.doc) for x in sa] == \
        [(len(x.prompt), x.new_tokens, x.doc) for x in sb]
    assert [len(d) for d in a.documents] == [len(d) for d in b.documents]
    assert not any(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(sa, sb))


def test_each_block_takes_every_slice_once():
    s = Stratified(8, np.random.default_rng(3))
    for _ in range(5):
        u = [s.next() for _ in range(8)]
        assert sorted(int(x * 8) for x in u) == list(range(8))


def test_requests_past_the_pool_repeat_its_lengths_with_new_tokens():
    mix = dict(load_mix("docqa-8k"), pool=10)
    t, specs, _ = _draw(mix, 2 ** 33 + 1, n=30)
    for i in range(t.clients, 20):
        a, b = specs[i], specs[i + 10]
        assert len(a.prompt) == len(b.prompt) and a.doc == b.doc
        assert a.new_tokens == b.new_tokens
        assert not np.array_equal(a.prompt[-16:], b.prompt[-16:])
    assert len(t.prompt_lengths()) <= 10


def test_lognormal_lengths_are_clipped():
    d = {"dist": "lognormal", "median": 256, "sigma": 1.0, "lo": 64,
         "hi": 1024}
    assert length(d, 1e-9) == 64 and length(d, 1 - 1e-9) == 1024
    assert length(d, 0.5) == 256
