"""End-to-end behaviour tests for the whole system (paper pipeline +
framework substrate wired together)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import predictive_sampling as ps
from repro.core import reparam
from repro.data.synthetic import binary_strokes, repetitive_tokens
from repro.models.pixelcnn import PixelCNN, PixelCNNConfig


def test_paper_pipeline_end_to_end():
    """Train ARM -> FPI sampling -> exactness -> call savings; the paper's
    core loop as one test."""
    cfg = PixelCNNConfig(height=8, width=8, channels=1, categories=2,
                         filters=12, n_res=1, first_kernel=5)
    data = jnp.asarray(binary_strokes(64, 8, 8, seed=0))
    params = PixelCNN.init(jax.random.PRNGKey(0), cfg)
    opt = optim.adamw(3e-3)
    state = opt.init(params)

    @jax.jit
    def step(params, state, batch):
        l, g = jax.value_and_grad(
            lambda p: PixelCNN.bpd(p, batch, cfg))(params)
        g = optim.zero_frozen(g)
        u, state = opt.update(g, state, params)
        return optim.apply_updates(params, u), state, l

    for _ in range(60):
        params, state, l = step(params, state, data)

    arm_fn = PixelCNN.make_arm_fn(params, cfg)
    eps = reparam.gumbel(jax.random.PRNGKey(1), (2, cfg.d, cfg.categories))
    x_ref, st_ref = ps.ancestral_sample(arm_fn, eps)
    x_fpi, st_fpi = ps.predictive_sample(arm_fn, ps.fpi_forecast, eps)
    np.testing.assert_array_equal(np.asarray(x_ref), np.asarray(x_fpi))
    assert int(st_fpi.arm_calls) < int(st_ref.arm_calls) // 2


def test_serving_pipeline_end_to_end():
    """Train LM -> engine generation windows 1 vs 8 -> exactness + savings."""
    from repro.configs import get_config
    from repro.engine import PredictiveSampler
    from repro.models.losses import lm_loss
    from repro.models.transformer import TransformerLM

    cfg = get_config("gemma3-1b", reduced=True)
    data = repetitive_tokens(64, 48, cfg.vocab, seed=0)
    params = TransformerLM.init(jax.random.PRNGKey(0), cfg)
    opt = optim.adamw(3e-3)
    state = opt.init(params)

    @jax.jit
    def step(params, state, batch):
        (l, _), g = jax.value_and_grad(
            lambda p: lm_loss(p, cfg, batch), has_aux=True)(params)
        g = optim.zero_frozen(g)
        u, state = opt.update(g, state, params)
        return optim.apply_updates(params, u), state, l

    rng = np.random.default_rng(0)
    for _ in range(80):
        params, state, l = step(params, state,
                                jnp.asarray(data[rng.integers(0, 64, 8)]))

    prompts = jnp.asarray(repetitive_tokens(2, 6, cfg.vocab, seed=9))
    ek = jax.random.PRNGKey(3)
    t1, s1 = PredictiveSampler(cfg, params, window=1, max_len=64,
                               eps_key=ek).generate(prompts, 20)
    t8, s8 = PredictiveSampler(cfg, params, window=8, max_len=64,
                               eps_key=ek).generate(prompts, 20)
    np.testing.assert_array_equal(np.asarray(t1[:, :26]),
                                  np.asarray(t8[:, :26]))
    assert s8["rounds"] < s1["rounds"]


def test_no_tp_rules_shard_everything_validly():
    from repro.configs import get_config
    from repro.models.transformer import TransformerLM
    from repro.sharding.rules import _leaf_spec_no_tp

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    cfg = get_config("internvl2-1b")
    params = jax.eval_shape(
        lambda: TransformerLM.init(jax.random.PRNGKey(0), cfg))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        names = [str(getattr(k, "key", getattr(k, "idx", ""))) for k in path]
        spec = _leaf_spec_no_tp(names, leaf, FakeMesh())
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            n = 256 if isinstance(ax, tuple) else 16
            assert leaf.shape[dim] % n == 0, (names, leaf.shape, spec)


def test_serve_exits_nonzero_and_reports_rejected_request(monkeypatch,
                                                         capsys):
    """A request that fails or is rejected makes the launcher exit
    nonzero, naming the request and its structured error."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
    rc = serve.main(["--arch", "qwen3-1.7b", "--reduced", "--requests", "1",
                     "--new-tokens", "100", "--max-len", "32"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "request 0 failed: too_long" in err


def test_serve_exits_zero_when_every_request_is_served(monkeypatch, capsys):
    from repro.launch import serve
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
    rc = serve.main(["--arch", "qwen3-1.7b", "--reduced", "--requests", "2",
                     "--new-tokens", "4", "--max-len", "32", "--batch", "2"])
    assert rc == 0
    assert "served 2 requests / 8 tokens" in capsys.readouterr().out


@pytest.mark.parametrize("env", [None, "/srv/jax-cache"])
def test_compile_cache_dir_is_fixed_or_from_env(monkeypatch, env):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without it
    the cache goes to the checkout's git-ignored ``.jax_cache``."""
    import pathlib

    from repro.launch import compile_cache
    set_calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: set_calls.append(a))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    got = compile_cache.use_compile_cache()
    root = pathlib.Path(__file__).resolve().parents[1]
    if env is None:
        assert got == str(root / ".jax_cache")
        assert set_calls == [("jax_compilation_cache_dir", got)]
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    else:
        assert got == env and set_calls == []
