"""Serving-engine tests: exactness of windowed predictive decode vs ancestral
(W=1), call savings on predictable streams, per-arch family coverage."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.engine import PredictiveSampler
from repro.models.transformer import TransformerLM

ARCH_SAMPLE = ["qwen3-1.7b", "deepseek-v3-671b", "rwkv6-7b",
               "jamba-1.5-large-398b", "gemma3-1b"]


def _make(arch, key=0):
    cfg = get_config(arch, reduced=True)
    params = TransformerLM.init(jax.random.PRNGKey(key), cfg)
    return cfg, params


@pytest.mark.parametrize("arch", ARCH_SAMPLE)
def test_window_exactness_vs_ancestral(arch):
    """W=8 predictive decode must emit bit-identical tokens to W=1 ancestral
    decode under the same eps stream — the paper's exactness claim, per
    architecture family (attention / MLA+MoE / RWKV / Mamba-hybrid / SWA)."""
    cfg, params = _make(arch)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, cfg.vocab)
    ek = jax.random.PRNGKey(42)
    new = 12

    s1 = PredictiveSampler(cfg, params, window=1, max_len=64, eps_key=ek)
    t1, st1 = s1.generate(prompts, new)
    s8 = PredictiveSampler(cfg, params, window=8, max_len=64, eps_key=ek)
    t8, st8 = s8.generate(prompts, new)

    np.testing.assert_array_equal(np.asarray(t1[:, :16]),
                                  np.asarray(t8[:, :16]))
    assert st1["rounds"] == new                      # ancestral: 1 call/token
    assert st8["rounds"] <= st1["rounds"]


def test_call_savings_on_peaked_model():
    """A near-deterministic LM (tiny logit temperature via scaled embeddings)
    must accept multi-token runs -> far fewer calls than tokens."""
    cfg, params = _make("qwen3-1.7b", key=3)
    # sharpen: scale the tied embedding table (peaks the output softmax)
    params = dict(params)
    params["embed"] = {"table": params["embed"]["table"] * 6.0}
    prompts = jnp.zeros((2, 2), jnp.int32)
    s = PredictiveSampler(cfg, params, window=8, max_len=96,
                          eps_key=jax.random.PRNGKey(0))
    toks, st = s.generate(prompts, 48)
    assert st["rounds"] < 48, st
    assert st["mean_accept"] > 1.0


def test_per_seq_calls_leq_rounds():
    cfg, params = _make("gemma-2b")
    prompts = jax.random.randint(jax.random.PRNGKey(5), (3, 3), 0, cfg.vocab)
    s = PredictiveSampler(cfg, params, window=4, max_len=64,
                          eps_key=jax.random.PRNGKey(1))
    _, st = s.generate(prompts, 10)
    assert (st["per_seq_calls"] <= st["rounds"]).all()


def test_forecast_heads_path_runs_and_is_exact():
    cfg, params = _make("deepseek-v3-671b")   # has forecast/MTP heads
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 3), 0, cfg.vocab)
    ek = jax.random.PRNGKey(7)
    s_ref = PredictiveSampler(cfg, params, window=1, max_len=48, eps_key=ek)
    t_ref, _ = s_ref.generate(prompts, 8)
    s_fc = PredictiveSampler(cfg, params, window=6, max_len=48, eps_key=ek,
                             use_forecast_heads=True)
    t_fc, st = s_fc.generate(prompts, 8)
    np.testing.assert_array_equal(np.asarray(t_ref[:, :11]),
                                  np.asarray(t_fc[:, :11]))


@pytest.mark.parametrize("arch", ["musicgen-large", "internvl2-1b",
                                  "dbrx-132b", "mistral-large-123b",
                                  "gemma-2b"])
def test_window_exactness_remaining_archs(arch):
    """Exactness for the rest of the zoo (audio/VLM/MoE/dense families)."""
    cfg, params = _make(arch, key=11)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 3), 0, cfg.vocab)
    ek = jax.random.PRNGKey(21)
    t1, _ = PredictiveSampler(cfg, params, window=1, max_len=48,
                              eps_key=ek).generate(prompts, 8)
    t6, _ = PredictiveSampler(cfg, params, window=6, max_len=48,
                              eps_key=ek).generate(prompts, 8)
    np.testing.assert_array_equal(np.asarray(t1[:, :11]),
                                  np.asarray(t6[:, :11]))


def test_verify_kernel_path_is_exact():
    """The Pallas spec_verify fast path must be bit-identical to the jnp
    verify (kernel <-> engine integration)."""
    cfg, params = _make("qwen3-1.7b", key=5)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, cfg.vocab)
    ek = jax.random.PRNGKey(33)
    t_ref, s_ref = PredictiveSampler(
        cfg, params, window=6, max_len=48, eps_key=ek).generate(prompts, 10)
    t_k, s_k = PredictiveSampler(
        cfg, params, window=6, max_len=48, eps_key=ek,
        use_verify_kernel=True).generate(prompts, 10)
    np.testing.assert_array_equal(np.asarray(t_ref[:, :14]),
                                  np.asarray(t_k[:, :14]))
    assert s_ref["rounds"] == s_k["rounds"]


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b", "rwkv6-7b"])
def test_low_memory_serve_step_equivalence(arch):
    """§Perf C4: the two-pass freeze-masked serve step must produce the same
    tokens, accepts AND recurrent states as the per-position path."""
    import jax.numpy as jnp
    from repro.launch.serve import make_serve_step

    cfg, params = _make(arch, key=13)
    B, W, S = 2, 5, 32
    cache = TransformerLM.init_cache(cfg, B, S, dtype=jnp.float32)
    # advance the cache a few tokens first so states are non-trivial
    toks0 = jax.random.randint(jax.random.PRNGKey(0), (B, 4), 0, cfg.vocab)
    _, _, nc = TransformerLM.decode_window(params, cfg, toks0, cache,
                                           jnp.zeros((B,), jnp.int32))
    cache = TransformerLM.select_states(cfg, nc, jnp.full((B,), 4,
                                                          jnp.int32))
    cand = jax.random.randint(jax.random.PRNGKey(1), (B, W), 0, cfg.vocab)
    clen = jnp.full((B,), 4, jnp.int32)
    eps = jax.random.gumbel(jax.random.PRNGKey(2), (B, W, cfg.vocab))

    out1, a1, c1 = jax.jit(make_serve_step(cfg, W))(params, cand, cache,
                                                    clen, eps)
    out2, a2, c2 = jax.jit(make_serve_step(cfg, W, low_memory=True))(
        params, cand, cache, clen, eps)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    for x, y in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-4, atol=2e-5)


def test_sampler_round_takes_params_as_arguments():
    """The jitted round gets the weights as an argument: weights it closed
    over would be lowered into the program as constants (gigabytes at
    published widths, which exhausts the host while compiling)."""
    cfg, params = _make("qwen3-1.7b")
    s = PredictiveSampler(cfg, params, window=4, max_len=32)
    state = s.init_state(jnp.ones((1, 3), jnp.int32), 1)
    traced = s._round.trace(s.params, state, jnp.full((1,), 8, jnp.int32))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert sum(np.size(c) for c in traced.jaxpr.consts) < n_params
