"""The plain reference against the serving program at reduced qwen3 sizes,
as configured and with an untied head and no qk-norm, on the CPU: engine prefill (with and without prefix-cache
hits) followed by paged decode agrees with the reference's full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model, reference

SMALL = dict(num_hidden_layers=2, hidden_size=256, intermediate_size=512,
             vocab_size=512, num_attention_heads=4, num_key_value_heads=2,
             head_dim=64, torch_dtype="float32")


VARIANTS = {"qwen3": {},
            "untied-no-qk-norm": {"tie_word_embeddings": False,
                                  "qk_norm": False}}


def small(variant):
    cfg = dict(model.load_config("qwen3-1.7b"))
    cfg.update(SMALL, **VARIANTS[variant])
    return cfg


def engine(cfg, seed, **kw):
    from repro.serving import ServingEngine
    params = model.program_params(cfg, seed)
    model.check_layout(cfg, params)
    return ServingEngine(model.model_config(cfg), params, batch=2,
                         window_max=4, max_len=128, block_size=8,
                         prefill_chunk=16,
                         eps_fn=model.make_eps_fn(cfg["vocab_size"]),
                         **kw)


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_prefill_then_paged_decode_matches_full_forward(name):
    from repro.models.transformer import PagedView, TransformerLM
    from repro.serving import Request
    cfg, seed = small(name), 3
    eng = engine(cfg, seed)
    prompt = np.random.default_rng(0).integers(0, 512, 37).astype(np.int32)
    eng.submit(Request(uid=0, prompt=prompt, new_tokens=20))
    eng.step()                          # admission prefill + one device loop
    n = int(eng.n_host[0])
    assert n > len(prompt)
    W = 4
    seq = np.asarray(eng.tokens[0, :n])
    assert np.array_equal(seq[:len(prompt)], prompt)
    cand = np.concatenate([seq[n - 1:], [5, 6, 7]])[None, :W]
    view = PagedView(jnp.asarray(eng.tables[:1]), jnp.asarray([0]), False)
    with jax.default_matmul_precision("highest"):
        logits, _, _ = TransformerLM.decode_window_paged(
            eng.params, eng.cfg, jnp.asarray(cand, jnp.int32), eng.paged,
            view, jnp.asarray([n - 1], jnp.int32))
    full = np.concatenate([seq, cand[0, 1:]])[None]
    ref = reference.Reference(cfg, seed).logits(
        full, [(0, p) for p in range(n, n + W)])
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_served_tokens_have_no_gap_in_f32(name):
    from repro.serving import Request
    cfg, seed = small(name), 5
    eng = engine(cfg, seed)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, 512, 40).astype(np.int32)
    reqs = [Request(uid=i, prompt=np.concatenate(
                [shared, rng.integers(0, 512, 5 + i).astype(np.int32)]),
                    new_tokens=24) for i in range(3)]
    eng.submit(reqs[0])
    eng.run()
    for r in reqs[1:]:                  # these hit the first one's blocks
        eng.submit(r)
    eng.run()
    assert all(r.ok for r in reqs)
    assert reqs[1].prefix_hit_blocks > 0 and reqs[2].prefix_hit_blocks > 0
    seqs = [(r.result, len(r.prompt), r.seq_id) for r in reqs]
    gaps, ctl = reference.served_gaps(cfg, seed, eng.eps_fn, seqs, 128,
                                      control=True)
    assert max(float(g.max()) for g in gaps) < 1e-3
    assert sum(len(g) for g in gaps) == 3 * 24
    assert all(len(c) == len(g) for c, g in zip(ctl, gaps))
