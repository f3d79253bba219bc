"""The dense GQA decoder family (Qwen3, Mistral): which configuration keys
the serving program takes, the random weights, and the work a token and a
paged-attention call need.

A configuration file names its family (``"family": "dense_gqa"``), and
``bench/model.py:family`` loads this module for it. Everything in the
benchmark that depends on the architecture lives here; another family is
another module beside this one with the same functions.

Weights are drawn by name and layer (``leaf(key, cfg, name, layer, dtype)``),
so that the plain reference can draw any single layer again, in float32,
without the program and without holding the whole model: the same function
on the same device gives the same numbers.

The counts are of the work a call *needs*, from its shapes and the lengths
it served, never from what an implementation happens to do: padding, tiles
past a row's length and rejected window positions add time but no count,
so a faster implementation of the same work raises the share.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from bench import model

# stable ids of the weight names (fold_in keys): never reorder, only append
NAMES = ("embed", "head", "final_norm", "attn_norm", "wq", "wk", "wv", "wo",
         "q_norm", "k_norm", "mlp_norm", "w_gate", "w_up", "w_down")
NORMS = ("final_norm", "attn_norm", "q_norm", "k_norm", "mlp_norm")
LAYER_NAMES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
               "mlp_norm", "w_gate", "w_up", "w_down")


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
    "name", "source", "family", "reference", "reduced", "assumed",
    "deployment", "check", "architectures", "model_type",
    "max_position_embeddings", "transformers_version", "bos_token_id",
    "eos_token_id", "pad_token_id", "initializer_range", "attention_dropout",
    "use_cache"})


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
            raise ValueError(f"{name}: key {k!r} is in none of "
                             "bench/families/dense_gqa.py's lists (carried, "
                             "held, descriptive)")
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


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

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

    return make(model.weights_key(seed))


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


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    """Matmul parameters a token passes through: every layer's attention
    projections and MLP, plus the output head (tied or not). The embedding
    lookup is a gather, not a matmul."""
    d, H, KV, hd, F = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def token_flops(cfg: dict, position: int) -> int:
    """Forward FLOPs one token at ``position`` needs: ``2 x`` matmul
    parameters plus ``4 L H d position`` of attention."""
    return (2 * matmul_params(cfg)
            + 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * int(position))


def span_flops(cfg: dict, start: int, end: int) -> int:
    """``token_flops`` summed over positions ``[start, end)``."""
    n = end - start
    if n <= 0:
        return 0
    pos_sum = (start + end - 1) * n // 2
    return (2 * matmul_params(cfg) * n
            + 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * pos_sum)


def paged_attn_call(cfg: dict, lengths, W: int, kv_bytes: int = 2,
                    act_bytes: int = 2):
    """(flops, bytes) the paged attention calls of one round need, one call
    a layer, for the rows in ``lengths`` (each row's cached length before
    the window; inactive rows are left out by the caller). Per row and
    layer: K and V of the ``length + W`` visible positions read once, the W
    queries read and the W outputs written, the W new K and V rows written;
    ``4 H W (length + W) d`` FLOPs (QK^T and PV)."""
    H, KV, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    flops = bytes_ = 0
    for ell in lengths:
        vis = int(ell) + W
        flops += 4 * H * W * vis * d
        bytes_ += (2 * vis * KV * d * kv_bytes           # K, V read
                   + 2 * W * H * d * act_bytes           # queries + output
                   + 2 * W * KV * d * kv_bytes)          # new K, V written
    L = cfg["num_hidden_layers"]
    return L * flops, L * bytes_


def paged_attn_prefill(cfg: dict, start: int, end: int, kv_bytes: int = 2,
                       act_bytes: int = 2):
    """(flops, bytes) every layer needs to prefill positions ``[start, end)``
    through the paged cache, however the engine splits them into calls:
    per layer K and V of positions ``< end`` read once, queries and outputs
    of the new positions, their K and V written; causal ``4 H d (q + 1)``
    FLOPs per query position ``q``."""
    H, KV, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    n = end - start
    if n <= 0:
        return 0, 0
    keys = (start + 1 + end) * n // 2                   # sum of (q + 1)
    flops = 4 * H * d * keys
    bytes_ = (2 * end * KV * d * kv_bytes + 2 * n * H * d * act_bytes
              + 2 * n * KV * d * kv_bytes)
    L = cfg["num_hidden_layers"]
    return L * flops, L * bytes_
