"""Expert-parallel MoE under shard_map (the distributed execution path).

Layout: tokens sharded over ("pod","data"); experts sharded over "model"
(EP) and FSDP-sharded over "data" on a weight dim. Each (data, model) shard:

  1. routes its local tokens (router weights replicated),
  2. keeps only assignments targeting its local experts, dispatches them
     into an (E/m, C, D) capacity buffer,
  3. all-gathers its expert weights over "data" (FSDP gather; the transpose
     reduce-scatters the gradients),
  4. computes the expert MLPs, scatters back weighted,
  5. psum over "model" combines contributions from all expert owners.

Communication per MoE layer = one (b, T, D) all-reduce over "model" + the
FSDP weight gathers — the baseline the §Perf all-to-all hillclimb improves
on (an all-to-all moves only routed tokens, ~k/E of the psum bytes... see
EXPERIMENTS.md §Perf for the actual napkin math and measurement).

The token-choice semantics (top-k, capacity, sort order) EXACTLY match the
single-device ``MoE.apply`` dense path — verified by
tests/sharding/test_moe_shard.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.moe import MoE, _mlp_apply


def _dp_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def moe_apply_sharded(p, x, cfg, mesh, capacity_factor, ep_only: bool = False):
    """Drop-in for MoE.apply under an active mesh. x: (B, T, D) sharded on
    batch; returns (y, aux).

    ``ep_only`` (§Perf C2, inference layout): experts sharded E-wise over
    ("model","data") jointly (full expert parallelism), weights NOT
    FSDP-sharded, tokens replicated (decode token sets are tiny) — removes
    the per-layer expert-weight all-gathers that dominate MoE decode."""
    dp = _dp_axes(mesh)
    n_model = mesh.shape["model"]
    n_data = mesh.shape["data"]
    E = cfg.n_experts

    if ep_only:
        if E % (n_model * n_data) == 0:
            e_axes, n_eshards = ("model", "data"), n_model * n_data
        else:
            e_axes, n_eshards = ("model",), n_model
        assert E % n_eshards == 0, (E, n_eshards)
        return _moe_ep_only(p, x, cfg, mesh, capacity_factor, e_axes,
                            n_eshards, dp)

    assert E % n_model == 0, (E, n_model)

    # batch not divisible by the dp extent (e.g. long_500k B=1): replicate
    # tokens over dp; expert parallelism over "model" still applies.
    dp_size = 1
    for ax in dp:
        dp_size *= mesh.shape[ax]
    x_spec = P(dp, None, None) if x.shape[0] % dp_size == 0 else P(None, None, None)
    w_spec = P("model", "data", None)
    r_spec = P()

    has_gate = "gate" in p["experts"]

    def local_fn(router, up, gate, down, x_local):
        m = jax.lax.axis_index("model")
        E_loc = E // n_model
        b, T, D = x_local.shape
        N = b * T
        k = cfg.top_k
        xf = x_local.reshape(N, D)

        # --- routing (identical math to MoE.route) -----------------------
        logits = (xf @ router).astype(jnp.float32)
        if cfg.router_score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(scores, k)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-9)
        probs = jax.nn.softmax(logits, axis=-1)
        aux = MoE.load_balance_loss(probs, ids.astype(jnp.int32), cfg)
        aux = jax.lax.pmean(aux, dp)

        if capacity_factor is None:
            C = N * k
        else:
            C = max(1, int(N * k * capacity_factor) // E)

        ids_flat = ids.reshape(N * k).astype(jnp.int32)
        w_flat = w.reshape(N * k)
        tok_flat = jnp.repeat(jnp.arange(N), k)
        order = jnp.argsort(ids_flat)
        ids_s = ids_flat[order]
        tok_s = tok_flat[order]
        w_s = w_flat[order]
        first = jnp.searchsorted(ids_s, ids_s, side="left")
        pos = jnp.arange(N * k) - first
        keep = pos < C
        pos_c = jnp.where(keep, pos, C)

        # --- local-expert dispatch --------------------------------------
        local = (ids_s // E_loc) == m
        e_loc = jnp.where(local, ids_s - m * E_loc, E_loc)  # E_loc -> drop
        buf = jnp.zeros((E_loc, C, D), x_local.dtype)
        buf = buf.at[e_loc, pos_c].set(xf[tok_s], mode="drop")

        # --- FSDP weight gather + expert MLPs ----------------------------
        up_f = jax.lax.all_gather(up, "data", axis=1, tiled=True)
        down_f = jax.lax.all_gather(down, "data", axis=1, tiled=True)
        hidden = jnp.einsum("ecd,edf->ecf", buf, up_f)
        if has_gate:
            gate_f = jax.lax.all_gather(gate, "data", axis=1, tiled=True)
            g = jnp.einsum("ecd,edf->ecf", buf, gate_f)
            act = (jax.nn.silu(g) if cfg.mlp_kind == "swiglu"
                   else jax.nn.gelu(g))
            hidden = hidden * act
        else:
            hidden = jax.nn.gelu(hidden)
        out = jnp.einsum("ecf,efd->ecd", hidden, down_f)

        # --- combine + cross-expert-owner reduction ----------------------
        gathered = out.at[e_loc, pos_c].get(mode="fill", fill_value=0.0)
        contrib = gathered * jnp.where(keep & local, w_s, 0.0)[:, None]
        y = jnp.zeros((N, D), x_local.dtype).at[tok_s].add(contrib)
        y = jax.lax.psum(y, "model")
        return y.reshape(b, T, D), aux

    gate_arg = p["experts"]["gate"] if has_gate else p["experts"]["up"]
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(r_spec, w_spec, w_spec, w_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p["router"]["w"], p["experts"]["up"], gate_arg,
      p["experts"]["down"], x)

    if "shared" in p:
        y = y + _mlp_apply(p["shared"], x.reshape(-1, x.shape[-1]),
                           cfg.mlp_kind).reshape(x.shape)
    return y, aux


def _moe_ep_only(p, x, cfg, mesh, capacity_factor, e_axes, n_eshards, dp):
    """Full expert parallelism for decode (§Perf C2). Tokens replicated;
    each shard owns E/n_eshards whole experts; one psum combines."""
    E, k = cfg.n_experts, cfg.top_k
    E_loc = E // n_eshards
    has_gate = "gate" in p["experts"]
    w_spec = P(e_axes if len(e_axes) > 1 else e_axes[0], None, None)
    x_spec = P(None, None, None)

    def local_fn(router, up, gate, down, x_rep):
        idx = jax.lax.axis_index(e_axes[0])
        if len(e_axes) > 1:
            idx = idx * mesh.shape[e_axes[1]] + jax.lax.axis_index(e_axes[1])
        b, T, D = x_rep.shape
        N = b * T
        xf = x_rep.reshape(N, D)

        logits = (xf @ router).astype(jnp.float32)
        if cfg.router_score == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        w, ids = jax.lax.top_k(scores, k)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-9)
        probs = jax.nn.softmax(logits, axis=-1)
        aux = MoE.load_balance_loss(probs, ids.astype(jnp.int32), cfg)

        C = N * k if capacity_factor is None else max(
            1, int(N * k * capacity_factor) // E)
        ids_flat = ids.reshape(N * k).astype(jnp.int32)
        w_flat = w.reshape(N * k)
        tok_flat = jnp.repeat(jnp.arange(N), k)
        order = jnp.argsort(ids_flat)
        ids_s, tok_s, w_s = ids_flat[order], tok_flat[order], w_flat[order]
        first = jnp.searchsorted(ids_s, ids_s, side="left")
        pos = jnp.arange(N * k) - first
        keep = pos < C
        pos_c = jnp.where(keep, pos, C)

        local = (ids_s // E_loc) == idx
        e_loc = jnp.where(local, ids_s - idx * E_loc, E_loc)
        buf = jnp.zeros((E_loc, C, D), x_rep.dtype)
        buf = buf.at[e_loc, pos_c].set(xf[tok_s], mode="drop")

        hidden = jnp.einsum("ecd,edf->ecf", buf, up)
        if has_gate:
            g = jnp.einsum("ecd,edf->ecf", buf, gate)
            act = (jax.nn.silu(g) if cfg.mlp_kind == "swiglu"
                   else jax.nn.gelu(g))
            hidden = hidden * act
        else:
            hidden = jax.nn.gelu(hidden)
        out = jnp.einsum("ecf,efd->ecd", hidden, down)

        gathered = out.at[e_loc, pos_c].get(mode="fill", fill_value=0.0)
        contrib = gathered * jnp.where(keep & local, w_s, 0.0)[:, None]
        y = jnp.zeros((N, D), x_rep.dtype).at[tok_s].add(contrib)
        y = jax.lax.psum(y, e_axes)
        return y.reshape(b, T, D), aux

    gate_arg = p["experts"]["gate"] if has_gate else p["experts"]["up"]
    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), w_spec, w_spec, w_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(p["router"]["w"], p["experts"]["up"], gate_arg,
      p["experts"]["down"], x)

    if "shared" in p:
        y = y + _mlp_apply(p["shared"], x.reshape(-1, x.shape[-1]),
                           cfg.mlp_kind).reshape(x.shape)
    return y, aux
