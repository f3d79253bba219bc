"""A whole run of the harness on the CPU at a small size, past its look for a
chip: sound, it comes out correct; with the timed path broken underneath it
comes out not correct."""
import json
import time

import pytest

from bench import model
from bench.run import ROOT, cell_metrics, run_cell
from bench.traffic import load_mix

SMALL = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
             vocab_size=512, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, torch_dtype="float32", check={"logit_gap": 1e-3})


def small_bench(traffic):
    """BENCHMARK.json's metrics with one cell of ``traffic`` on qwen3."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": f"small.{traffic}", "config": "qwen3-1.7b",
                           "traffic": traffic, "chips": 1, "why": "test"}]
    return bench


def small_run(traffic="chat-poisson", seed=2 ** 31 + 11):
    bench = small_bench(traffic)
    cfg = dict(model.load_config("qwen3-1.7b"))
    cfg.update(SMALL)
    mix = load_mix(traffic)
    mix.update(strata=2, warmup_s=0.5,
               engine={"batch": 4, "window_max": 2, "block_size": 8,
                       "max_len": 128, "prefill_chunk": 8},
               check={"tokens": 32, "max_requests": 4})
    mix["prompt"].update(lo=8, hi=24, median=12)
    mix["output"].update(lo=8, hi=24, median=12)
    if "documents" in mix:
        mix["documents"] = {"count": 2,
                            "length": {"dist": "uniform", "lo": 40, "hi": 60}}
    if "clients" in mix:
        mix["clients"] = 4
    if "rate_per_s" in mix:
        mix["rate_per_s"] = 40.0
    return run_cell(bench, f"small.{traffic}", seed, 2.0, False, cfg=cfg,
                    mix=mix, t_process=time.monotonic())


@pytest.mark.parametrize("traffic", ["chat-poisson", "docqa-8k"])
def test_sound_run_is_correct(traffic):
    out = small_run(traffic)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell_metrics(
        small_bench(traffic), f"small.{traffic}", False)}
    assert list(out)[-1] == "checks"


def test_altered_token_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from repro.engine import spec_decode

    real = spec_decode.reparam_argmax

    # every token id divisible by 3 is replaced by its neighbour: a third of
    # the tokens, so that the 32 or more checked all come out clean with a
    # chance of about (2/3)**32, 2e-6, whichever requests finish in time
    def altered(logits, eps):
        out = real(logits, eps)
        return jnp.where(out % 3 == 0, (out + 1) % logits.shape[-1], out)

    monkeypatch.setattr(spec_decode, "reparam_argmax", altered)
    out = small_run()
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > 1e-3


def test_step_that_leaves_state_unchanged_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from repro.serving import engine as engine_mod

    def frozen(params, cfg, eps_fn, state, target, **kw):
        B = state.n.shape[0]
        z = jnp.zeros((B,), jnp.int32)
        return state, jnp.stack([z, z, state.n, z], axis=1)

    monkeypatch.setattr(engine_mod, "verify_round", frozen)
    out = small_run()
    assert not out["correct"]
