"""Configurations, seeds and noise of the benchmark, whatever the
architecture.

A configuration is a JSON file under ``bench/configs`` that keeps the keys of
the model's public ``config.json`` and names its architecture family
(``"family"``): a module ``bench/families/<family>.py`` that turns the file
into the serving program's ``ModelConfig``, refusing a file that states what
the program cannot express, draws the weights from the run's seed, and
counts the work a token and an attention call need. ``family`` loads it.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent
FAMILIES = BENCH / "families"


def load_config(name: str) -> dict:
    """The configuration file ``bench/configs/<name>.json``."""
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def family(cfg: dict):
    """The module of the family the configuration names,
    ``bench/families/<family>.py``. Raises ``ValueError``, naming the key,
    where the file names none or one that is not there."""
    name = cfg.get("family")
    path = FAMILIES / f"{name}.py"
    if not (isinstance(name, str) and re.fullmatch(r"[A-Za-z0-9_]+", name)
            and path.is_file()):
        raise ValueError(f"{cfg.get('name')}: key 'family' is {name!r}; it "
                         "has to name a module under bench/families/")
    return _load_family(path)


@functools.cache
def _load_family(path: Path):
    return load_module(path, f"bench_family_{path.stem}")


def load_module(path: Path, name: str):
    """The Python file at ``path``, imported as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def jax_seed(seed: int, stream: str) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from any whole number and a
    stream name (weights, noise, traffic draw separate streams)."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 64,
                                 sum(ord(c) << (8 * i)
                                     for i, c in enumerate(stream))])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


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
