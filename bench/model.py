"""Configurations and random weights of the benchmark's dense GQA models.

A configuration is a JSON file under ``bench/configs`` that keeps the keys of
the model's public ``config.json``. This module turns it into the serving
program's ``ModelConfig`` and draws the weights from the run's seed, on the
device, in one jitted call, in the type they are served in.

Weights are drawn by name and layer (``leaf(name, layer)``), so that the
plain reference can draw any single layer again, in float32, without the
program and without holding the whole model: the same function on the same
device gives the same numbers.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent

# stable ids of the weight names (fold_in keys): never reorder, only append
NAMES = ("embed", "head", "final_norm", "attn_norm", "wq", "wk", "wv", "wo",
         "q_norm", "k_norm", "mlp_norm", "w_gate", "w_up", "w_down")
NORMS = ("final_norm", "attn_norm", "q_norm", "k_norm", "mlp_norm")
LAYER_NAMES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
               "mlp_norm", "w_gate", "w_up", "w_down")


def load_config(name: str) -> dict:
    """The configuration file ``bench/configs/<name>.json``."""
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def jax_seed(seed: int, stream: str) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from any whole number and a
    stream name (weights, noise, traffic draw separate streams)."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 64,
                                 sum(ord(c) << (8 * i)
                                     for i, c in enumerate(stream))])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


def model_config(cfg: dict):
    """The serving program's ``ModelConfig`` for a configuration file."""
    from repro.models.transformer import ModelConfig
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{cfg['name']}: only SwiGLU MLPs are modelled")
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_block=(("attn", "dense"),), qk_norm=bool(cfg.get("qk_norm")),
        rope_theta=float(cfg["rope_theta"]), mlp_kind="swiglu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"], source=cfg["source"])


def leaf_shapes(cfg: dict) -> dict:
    """Shape of every weight by name; layer weights per layer."""
    d, H, KV, hd, F, V = (cfg["hidden_size"], cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"],
                          cfg["intermediate_size"], cfg["vocab_size"])
    s = {"embed": (V, d), "final_norm": (d,), "attn_norm": (d,),
         "wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd),
         "wo": (H * hd, d), "mlp_norm": (d,), "w_gate": (d, F),
         "w_up": (d, F), "w_down": (F, d)}
    if not cfg["tie_word_embeddings"]:
        s["head"] = (d, V)
    if cfg.get("qk_norm"):
        s["q_norm"] = s["k_norm"] = (hd,)
    return s


ROWS = 128      # rows drawn at a time: a draw holds only this slice in f32


def _chunk(key, cfg: dict, name: str, layer, i, dtype):
    """Rows ``[i * ROWS, (i + 1) * ROWS)`` of one weight (all of a vector),
    drawn from ``key`` by name, layer and slice. Matrices are normal with
    std ``1/sqrt(fan_in)``; a tied embedding, which is also the head, has std
    ``1/sqrt(d)`` and an untied one std 1, so that logits and the first
    layer's input have unit scale; norm scales are ``1 + 0.1 N``."""
    shape = leaf_shapes(cfg)[name]
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        key, NAMES.index(name)), layer), i)
    rows = shape if len(shape) == 1 else (min(ROWS, shape[0]),) + shape[1:]
    x = jax.random.normal(k, rows, jnp.float32)
    if name in NORMS:
        x = 1.0 + 0.1 * x
    elif name == "embed":
        x = x * (1.0 / math.sqrt(shape[1])
                 if cfg["tie_word_embeddings"] else 1.0)
    else:
        x = x * (1.0 / math.sqrt(shape[0]))
    return x.astype(dtype)


def _fill(buf, key, cfg: dict, name: str, layer, dtype, lead=()):
    """Write one weight into ``buf[lead]`` slice by slice."""
    shape = leaf_shapes(cfg)[name]
    if len(shape) == 1 or shape[0] <= ROWS:
        return buf.at[lead].set(_chunk(key, cfg, name, layer, 0, dtype))
    if shape[0] % ROWS:
        raise ValueError(f"{name}: {shape[0]} rows, not a multiple of {ROWS}")
    zero = (0,) * (len(shape) - 1)

    def body(i, b):
        x = _chunk(key, cfg, name, layer, i, dtype)
        return jax.lax.dynamic_update_slice(
            b, x.reshape((1,) * len(lead) + x.shape),
            tuple(lead) + (i * ROWS,) + zero)
    return jax.lax.fori_loop(0, shape[0] // ROWS, body, buf)


def leaf(key, cfg: dict, name: str, layer, dtype):
    """One weight of one layer (``layer`` 0 for the others)."""
    return _fill(jnp.zeros(leaf_shapes(cfg)[name], dtype), key, cfg, name,
                 layer, dtype)


def layer_leaves(key, cfg: dict, layer, dtype) -> dict:
    names = [n for n in LAYER_NAMES if n in leaf_shapes(cfg)]
    return {n: leaf(key, cfg, n, layer, dtype) for n in names}


def weights_key(seed: int):
    return jax.random.PRNGKey(jax_seed(seed, "weights"))


def noise_seed(seed: int, uid: int) -> int:
    """A request's noise stream (``Request.noise_seed``), drawn from the
    run's seed and the request's id."""
    return jax_seed(seed, f"noise-{uid}")


def make_eps_fn(vocab: int):
    """The reparametrization noise, handed to the engine and used again by
    the reference: ``eps_fn(seq_ids (B,), positions (B, W))`` gives Gumbel
    noise ``(B, W, V)`` fixed per (noise stream, position), so a served
    token is the argmax of its logits plus this noise. The key is the same
    in every run (the run's seed enters through the streams, see
    ``noise_seed``): a key drawn from the seed would be a constant of every
    round program, and each seed would compile them anew."""
    key = jax.random.PRNGKey(0)

    def eps_fn(seq_ids, positions):
        def one(sid, row):
            ks = jax.random.fold_in(key, sid)
            return jax.vmap(lambda p: jax.random.gumbel(
                jax.random.fold_in(ks, p), (vocab,), jnp.float32))(row)
        return jax.vmap(one)(seq_ids, positions)
    return eps_fn


def program_params(cfg: dict, seed: int):
    """The serving program's parameter pytree, drawn on the default device
    in one jitted call, in the configuration's dtype."""
    dtype = jnp.dtype(cfg["torch_dtype"])
    L = cfg["num_hidden_layers"]

    @jax.jit
    def make(key):
        # every layer's slices written in place: a draw holds one slice
        def stacked(n):
            return jax.lax.fori_loop(
                0, L, lambda l, b: _fill(b, key, cfg, n, l, dtype, (l,)),
                jnp.zeros((L,) + leaf_shapes(cfg)[n], dtype))

        st = {n: stacked(n) for n in LAYER_NAMES if n in leaf_shapes(cfg)}
        mixer = {"wq": {"w": st["wq"]}, "wk": {"w": st["wk"]},
                 "wv": {"w": st["wv"]}, "wo": {"w": st["wo"]}}
        if "q_norm" in st:
            mixer["q_norm"] = {"scale": st["q_norm"]}
            mixer["k_norm"] = {"scale": st["k_norm"]}
        params = {
            "embed": {"table": leaf(key, cfg, "embed", 0, dtype)},
            "prefix": [],
            "blocks": [{
                "norm1": {"scale": st["attn_norm"]},
                "mixer": mixer,
                "norm2": {"scale": st["mlp_norm"]},
                "ffn": {"up": {"w": st["w_up"]}, "down": {"w": st["w_down"]},
                        "gate": {"w": st["w_gate"]}}}],
            "suffix": [],
            "final_norm": {"scale": leaf(key, cfg, "final_norm", 0, dtype)},
        }
        if not cfg["tie_word_embeddings"]:
            params["head"] = {"w": leaf(key, cfg, "head", 0, dtype)}
        return params

    return make(weights_key(seed))


def check_layout(cfg: dict, params) -> None:
    """Raise if ``params`` is not laid out as the program's own init lays
    out its parameters (names, shapes and dtypes)."""
    from repro.models.transformer import TransformerLM
    want = jax.eval_shape(lambda k: TransformerLM.init(k, model_config(cfg)),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{cfg['name']}: weights do not match the program's "
                         "parameter layout")
