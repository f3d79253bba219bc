"""A configuration reaches everything that depends on its architecture (the
keys the program takes, the weights, the work counts) through the family it
names: a new family is one more module under ``bench/families/``, which the
harness finds by the configuration's ``family`` key."""
import textwrap
import time
from types import SimpleNamespace

import pytest

from bench import model, roofline
from bench.run import Run, load_reader, run_cell

TOY = textwrap.dedent('''
    """A family of no real architecture: it records what reaches it."""
    CALLS = []


    def model_config(cfg, max_len=None):
        CALLS.append(("model_config", max_len))
        return "toy ModelConfig"


    def program_params(cfg, seed):
        CALLS.append(("program_params", seed))
        raise RuntimeError("toy weights")


    def span_flops(cfg, start, end):
        CALLS.append(("span_flops", start, end))
        return 1000 * (end - start)


    def paged_attn_call(cfg, lengths, W):
        CALLS.append(("paged_attn_call", tuple(lengths), W))
        return 0, 819e9 * len(lengths)          # a second a row at 819 GB/s


    def paged_attn_prefill(cfg, start, end):
        CALLS.append(("paged_attn_prefill", start, end))
        return 0, 819e9 * (end - start)
''')

BENCH = {"workloads": [{"name": "toy.docqa-8k", "config": "toy",
                        "traffic": "docqa-8k", "chips": 1}],
         "end_to_end": [], "per_layer": []}


@pytest.fixture
def toy(tmp_path, monkeypatch):
    (tmp_path / "toy.py").write_text(TOY)
    monkeypatch.setattr(model, "FAMILIES", tmp_path)
    cfg = {"name": "toy", "family": "toy", "vocab_size": 512}
    return cfg, model.family(cfg)


def test_toy_family_is_reached_for_keys_weights_and_counts(toy):
    cfg, fam = toy
    with pytest.raises(RuntimeError, match="toy weights"):
        run_cell(BENCH, "toy.docqa-8k", 2 ** 31 + 7, 1.0, False, cfg=cfg,
                 t_process=time.monotonic())
    assert fam.CALLS == [("model_config", 9216),
                         ("program_params", 2 ** 31 + 7)]

    # one step in the window: two rows, the first active for both rounds,
    # and a 64-token prefill span
    step = SimpleNamespace(t0=1.0, t1=2.0, rounds=2, window=8,
                           rows=[(100, 2, 3), (50, 1, 1)], prefills=[(0, 64)])
    trace = SimpleNamespace(window_s=2.0, classified=True,
                            kernels={"paged_attention": 134.0})
    run = Run(cfg=cfg, steps=[step], window=(0.5, 2.5), trace=trace,
              peaks=roofline.peaks_for("TPU v5 lite"))
    del fam.CALLS[:]
    assert load_reader("mfu_pct")(run) == pytest.approx(
        100.0 * 1000 * (3 + 1 + 64) / (2.0 * 197e12))
    assert fam.CALLS == [("span_flops", 101, 104), ("span_flops", 51, 52),
                         ("span_flops", 0, 64)]
    del fam.CALLS[:]
    # least time 2 + 1 s of rounds and 64 s of prefill over 134 s
    assert load_reader("paged_attn_roofline")(run) == pytest.approx(50.0)
    assert fam.CALLS == [("paged_attn_call", (100, 50), 8),
                         ("paged_attn_call", (100,), 8),
                         ("paged_attn_prefill", 0, 64)]


@pytest.mark.parametrize("family", [None, "no_such_family", "../model",
                                    "dense-gqa", 7])
def test_missing_or_unknown_family_is_refused(family):
    cfg = dict(model.load_config("qwen3-1.7b"))
    if family is None:
        del cfg["family"]
    else:
        cfg["family"] = family
    with pytest.raises(ValueError, match="'family'"):
        model.family(cfg)


def test_run_refuses_an_unknown_family_before_any_weight(toy):
    cfg, fam = toy
    with pytest.raises(ValueError, match="'family'"):
        run_cell(BENCH, "toy.docqa-8k", 3, 1.0, False,
                 cfg=dict(cfg, family="not_here"),
                 t_process=time.monotonic())
    assert fam.CALLS == []
