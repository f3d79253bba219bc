"""Configurations and random weights of the benchmark's dense GQA models.

A configuration is a JSON file under ``bench/configs`` that keeps the keys of
the model's public ``config.json``. This module turns it into the serving
program's ``ModelConfig``, refusing a file that states what the program
cannot express, and draws the weights from the run's seed, on the
device, in one jitted call, in the type they are served in.

Weights are drawn by name and layer (``leaf(name, layer)``), so that the
plain reference can draw any single layer again, in float32, without the
program and without holding the whole model: the same function on the same
device gives the same numbers.
"""
from __future__ import annotations

import dataclasses
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


# Every key of a configuration file is in exactly one of three lists
# (``key_lists``), and ``model_config`` refuses a key in none of them, so a
# configuration is served as its file states it or not at all.
#
# carried: the value goes to the ``ModelConfig`` field named here (with the
# conversion given), where the program declares that field.
CARRIED = {
    "num_hidden_layers": ("n_layers", int), "hidden_size": ("d_model", int),
    "intermediate_size": ("d_ff", int), "vocab_size": ("vocab", int),
    "num_attention_heads": ("n_heads", int),
    "num_key_value_heads": ("n_kv_heads", int),
    "head_dim": ("head_dim", int), "qk_norm": ("qk_norm", bool),
    "rope_theta": ("rope_theta", float),
    "tie_word_embeddings": ("tie_embeddings", bool),
    "torch_dtype": ("dtype", str), "rms_norm_eps": ("norm_eps", float)}
CARRIED_DEFAULTS = {"qk_norm": False}     # the only carried key a file may omit
# held: the program fixes the value, so the file may state that value only
# (or omit the key). A carried key whose field the program does not declare
# is held at the value the program uses: the RMSNorm eps of
# ``repro.nn.core.RMSNorm``.
HELD = {"rms_norm_eps": 1e-6, "hidden_act": "silu", "attention_bias": False,
        "mlp_bias": False, "rope_scaling": None, "sliding_window": None,
        "use_sliding_window": False}
# descriptive: labels and settings that cannot change the served forward
# pass (dropout and initialisation act in training only; token ids are the
# tokenizer's). ``max_position_embeddings`` is checked against ``max_len``.
DESCRIPTIVE = frozenset({
    "name", "source", "reference", "reduced", "assumed", "deployment", "check",
    "architectures", "model_type", "max_position_embeddings",
    "transformers_version", "bos_token_id", "eos_token_id", "pad_token_id",
    "initializer_range", "attention_dropout", "use_cache"})


def key_lists(config_class) -> tuple[dict, dict, frozenset]:
    """The carried, held and descriptive keys for the program's config
    class: ``({key: (field, conversion)}, {key: value}, keys)``."""
    fields = {f.name for f in dataclasses.fields(config_class)}
    carried = {k: v for k, v in CARRIED.items() if v[0] in fields}
    held = {k: v for k, v in HELD.items() if k not in carried}
    return carried, held, DESCRIPTIVE


def model_config(cfg: dict, max_len: int | None = None):
    """The serving program's ``ModelConfig`` for a configuration file, built
    from the file's carried keys. Raises ``ValueError``, naming the key, for
    a key in none of the three lists, a held key at another value than the
    program's, a missing carried key, or a ``max_position_embeddings``
    below ``max_len`` (the engine's, where given)."""
    from repro.models.transformer import ModelConfig
    carried, held, descriptive = key_lists(ModelConfig)
    name = cfg.get("name")
    for k in sorted(cfg):
        if k not in carried and k not in held and k not in descriptive:
            raise ValueError(f"{name}: key {k!r} is in none of bench/model.py's"
                             " lists (carried, held, descriptive)")
        if k in held and cfg[k] != held[k]:
            raise ValueError(f"{name}: {k} is {cfg[k]!r}; the program fixes it"
                             f" at {held[k]!r} and has no field to carry "
                             "another value")
    for k in sorted(set(CARRIED) - set(CARRIED_DEFAULTS)):
        if k not in cfg:
            raise ValueError(f"{name}: key {k!r} is missing")
    limit = cfg.get("max_position_embeddings")
    if max_len is not None and limit is not None and max_len > limit:
        raise ValueError(f"{name}: max_len {max_len} exceeds "
                         f"max_position_embeddings {limit}")
    fields = {f: conv(cfg.get(k, CARRIED_DEFAULTS.get(k)))
              for k, (f, conv) in carried.items()}
    return ModelConfig(name=cfg["name"], arch_type="dense",
                       layer_block=(("attn", "dense"),), mlp_kind="swiglu",
                       source=cfg["source"], **fields)


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
