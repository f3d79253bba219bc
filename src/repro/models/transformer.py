"""Generic decoder stack assembled from a ModelConfig.

One model class covers all 10 assigned architectures: the per-layer spec
(mixer kind x FFN kind) is laid out as ``prefix + n_blocks * block + suffix``
so homogeneous segments compile as a single ``lax.scan`` body (essential —
the 61..88-layer dry-run configs would otherwise produce enormous HLO).

Modes:
* ``apply``         — full-sequence forward (training / prefill); returns
                      (logits, h, aux) where ``h`` is the shared penultimate
                      representation (paper §2.2) feeding forecasting/MTP
                      heads.
* ``decode_window`` — W verify tokens against per-layer caches/state
                      snapshots (predictive-sampling serving step).

Multimodal backbones (audio/VLM) consume stub frontend embeddings as a
prefix (see frontends.py and DESIGN.md carve-out).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import GQAttention, MLAttention
from repro.models.moe import MoE
from repro.models.ssm import Mamba, RWKV6ChannelMix, RWKV6TimeMix
from repro.nn.core import Dense, Embedding, RMSNorm
from repro.sharding import constrain


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

LayerSpec = tuple  # (mixer: str, ffn: str); mixer in {attn, local, mla,
#                    mamba, rwkv}; ffn in {dense, moe, rwkv_cmix}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                      # dense|moe|ssm|hybrid|vlm|audio
    n_layers: int
    d_model: int
    d_ff: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    # layer layout
    layer_prefix: tuple = ()
    layer_block: tuple = (("attn", "dense"),)
    layer_suffix: tuple = ()
    # attention
    qk_norm: bool = False
    rope_theta: float = 10000.0
    sliding_window: int = 0             # for "local" mixer layers
    # MLP
    mlp_kind: str = "swiglu"            # swiglu|geglu|gelu
    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    router_score: str = "softmax"       # softmax|sigmoid
    # MLA
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0
    # SSM
    ssm_state: int = 16
    rwkv_head_dim: int = 64
    # embeddings / head
    tie_embeddings: bool = True
    embed_scale: bool = False           # gemma: h *= sqrt(d_model)
    # forecasting / MTP (the paper's learned-forecasting integration)
    forecast_horizon: int = 0
    forecast_hidden: int = 0
    forecast_loss_weight: float = 0.01  # paper Appendix A
    # multimodal stub frontend
    modality: str = "text"              # text|audio|vision
    n_prefix_tokens: int = 0            # frontend embedding count
    # numerics
    dtype: str = "float32"
    # documentation
    source: str = ""

    @property
    def n_blocks(self) -> int:
        per = len(self.layer_block)
        rem = self.n_layers - len(self.layer_prefix) - len(self.layer_suffix)
        assert rem % per == 0, (self.name, rem, per)
        return rem // per

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)

    def layer_specs(self):
        return (list(self.layer_prefix)
                + list(self.layer_block) * self.n_blocks
                + list(self.layer_suffix))


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------

_MIXERS = {
    "attn": GQAttention,
    "local": GQAttention,
    "mla": MLAttention,
    "mamba": Mamba,
    "rwkv": RWKV6TimeMix,
}


def _layer_init(key, spec: LayerSpec, cfg: ModelConfig, dtype):
    mixer, ffn = spec
    k1, k2 = jax.random.split(key)
    p = {"norm1": RMSNorm.init(cfg.d_model, dtype=dtype),
         "mixer": _MIXERS[mixer].init(k1, cfg, dtype=dtype),
         "norm2": RMSNorm.init(cfg.d_model, dtype=dtype)}
    if ffn == "dense":
        from repro.models.moe import _mlp_init
        p["ffn"] = _mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind, dtype)
    elif ffn == "moe":
        p["ffn"] = MoE.init(k2, cfg, dtype=dtype)
    elif ffn == "rwkv_cmix":
        p["ffn"] = RWKV6ChannelMix.init(k2, cfg, dtype=dtype)
    else:
        raise ValueError(ffn)
    return p


def _layer_full(p, spec: LayerSpec, cfg: ModelConfig, h, aux,
                moe_capacity=None):
    mixer, ffn = spec
    u = RMSNorm.apply(p["norm1"], h)
    if mixer in ("attn", "local"):
        window = cfg.sliding_window if mixer == "local" else 0
        y = GQAttention.full(p["mixer"], u, cfg, window=window)
    elif mixer == "mla":
        y = MLAttention.full(p["mixer"], u, cfg)
    elif mixer == "mamba":
        y = Mamba.full(p["mixer"], u, cfg)
    elif mixer == "rwkv":
        y = RWKV6TimeMix.full(p["mixer"], u, cfg)
    h = h + y
    h = constrain(h, ("batch", "seq", "embed"))

    v = RMSNorm.apply(p["norm2"], h)
    if ffn == "dense":
        from repro.models.moe import _mlp_apply
        z = _mlp_apply(p["ffn"], v, cfg.mlp_kind)
    elif ffn == "moe":
        z, moe_aux = MoE.apply(p["ffn"], v, cfg, capacity_factor=moe_capacity)
        aux = aux + moe_aux
    elif ffn == "rwkv_cmix":
        z = RWKV6ChannelMix.full(p["ffn"], v, cfg)
    h = h + z
    h = constrain(h, ("batch", "seq", "embed"))
    return h, aux


def _layer_cache_init(spec: LayerSpec, cfg: ModelConfig, batch, max_len,
                      dtype):
    mixer, ffn = spec
    c = {}
    if mixer in ("attn", "local"):
        c["mixer"] = GQAttention.init_cache(cfg, batch, max_len, dtype)
    elif mixer == "mla":
        c["mixer"] = MLAttention.init_cache(cfg, batch, max_len, dtype)
    elif mixer == "mamba":
        c["mixer"] = Mamba.init_state(cfg, batch, dtype)
    elif mixer == "rwkv":
        c["mixer"] = RWKV6TimeMix.init_state(cfg, batch, dtype)
    if ffn == "rwkv_cmix":
        c["ffn"] = RWKV6ChannelMix.init_state(cfg, batch, dtype)
    return c


class PagedView(NamedTuple):
    """Block-table addressing for a paged decode step: attention cache leaves
    are the shared physical pools and each of the R view rows reads/writes
    through ``tables``; ``rows`` selects the batch slots whose (un-paged)
    recurrent states ride along. ``use_kernel`` picks the Pallas paged
    flash-decode kernel over the gather-view CPU-exact fallback."""
    tables: Any                        # (R, nb) physical block ids
    rows: Any                          # (R,) batch slots
    use_kernel: bool = False
    interpret: Optional[bool] = None


def _layer_window(p, spec: LayerSpec, cfg: ModelConfig, h, cache, cache_len,
                  state_mode: str = "per_position", accept=None,
                  paged: Optional[PagedView] = None):
    """Returns (h, new_cache).

    state_mode:
      * "per_position" — recurrent mixers return states at every window
        position (extra W axis); engine selects via ``select_states``.
      * "none"    — logits-only pass: recurrent caches pass through
        unchanged (per-position stacks are DCE'd). First pass of the
        two-pass low-memory decode (§Perf C4).
      * "advance" — recurrent mixers return ONLY the state after ``accept``
        (B,) tokens (freeze-masked scan; second pass of C4).

    With ``paged``, attention/local/mla cache entries are physical block
    pools addressed through ``paged.tables`` (recurrent mixers are identical
    in both modes — their per-slot states are never paged).
    """
    mixer, ffn = spec
    new_cache = {}
    u = RMSNorm.apply(p["norm1"], h)
    if mixer in ("attn", "local"):
        window = cfg.sliding_window if mixer == "local" else 0
        if paged is not None:
            y, new_cache["mixer"] = GQAttention.window_paged(
                p["mixer"], u, cfg, cache["mixer"], paged.tables, cache_len,
                window=window, use_kernel=paged.use_kernel,
                interpret=paged.interpret)
        else:
            y, new_cache["mixer"] = GQAttention.window(
                p["mixer"], u, cfg, cache["mixer"], cache_len, window=window)
    elif mixer == "mla":
        if paged is not None:
            y, new_cache["mixer"] = MLAttention.window_paged(
                p["mixer"], u, cfg, cache["mixer"], paged.tables, cache_len,
                use_kernel=paged.use_kernel, interpret=paged.interpret)
        else:
            y, new_cache["mixer"] = MLAttention.window(
                p["mixer"], u, cfg, cache["mixer"], cache_len)
    elif mixer == "mamba":
        y, st = Mamba.window(p["mixer"], u, cfg, cache["mixer"])
        if state_mode == "per_position":
            new_cache["mixer"] = st
        elif state_mode == "none":
            new_cache["mixer"] = cache["mixer"]
        else:
            new_cache["mixer"] = Mamba.advance_state(
                p["mixer"], u, cfg, cache["mixer"], accept)
    elif mixer == "rwkv":
        y, st = RWKV6TimeMix.window(p["mixer"], u, cfg, cache["mixer"])
        if state_mode == "per_position":
            new_cache["mixer"] = st
        elif state_mode == "none":
            new_cache["mixer"] = cache["mixer"]
        else:
            new_cache["mixer"] = RWKV6TimeMix.advance_state(
                p["mixer"], u, cfg, cache["mixer"], accept)
    h = h + y

    v = RMSNorm.apply(p["norm2"], h)
    if ffn == "dense":
        from repro.models.moe import _mlp_apply
        z = _mlp_apply(p["ffn"], v, cfg.mlp_kind)
    elif ffn == "moe":
        z, _ = MoE.apply(p["ffn"], v, cfg, capacity_factor=None)
    elif ffn == "rwkv_cmix":
        y2, st2 = RWKV6ChannelMix.window(p["ffn"], v, cfg, cache["ffn"])
        z = y2
        if state_mode == "per_position":
            new_cache["ffn"] = st2
        elif state_mode == "none":
            new_cache["ffn"] = cache["ffn"]
        else:
            new_cache["ffn"] = RWKV6ChannelMix.advance_state(
                p["ffn"], v, cfg, cache["ffn"], accept)
    h = h + z
    return h, new_cache


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------

class TransformerLM:
    @staticmethod
    def init(key, cfg: ModelConfig):
        dtype = cfg.param_dtype
        k_emb, k_pre, k_blk, k_suf, k_head, k_fc = jax.random.split(key, 6)
        params = {"embed": Embedding.init(k_emb, cfg.vocab, cfg.d_model,
                                          dtype=dtype)}
        params["prefix"] = [
            _layer_init(k, spec, cfg, dtype)
            for k, spec in zip(jax.random.split(k_pre,
                                                max(1, len(cfg.layer_prefix))),
                               cfg.layer_prefix)]
        if cfg.n_blocks:
            def init_block(k):
                ks = jax.random.split(k, len(cfg.layer_block))
                return [_layer_init(kk, spec, cfg, dtype)
                        for kk, spec in zip(ks, cfg.layer_block)]
            blocks = [init_block(k)
                      for k in jax.random.split(k_blk, cfg.n_blocks)]
            params["blocks"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *blocks)
        params["suffix"] = [
            _layer_init(k, spec, cfg, dtype)
            for k, spec in zip(jax.random.split(k_suf,
                                                max(1, len(cfg.layer_suffix))),
                               cfg.layer_suffix)]
        params["final_norm"] = RMSNorm.init(cfg.d_model, dtype=dtype)
        if not cfg.tie_embeddings:
            params["head"] = Dense.init(k_head, cfg.d_model, cfg.vocab,
                                        use_bias=False, dtype=dtype)
        if cfg.forecast_horizon:
            from repro.core.forecasting import TokenForecast, TokenForecastConfig
            params["forecast"] = TokenForecast.init(
                k_fc, TokenForecastConfig(cfg.d_model, cfg.vocab,
                                          cfg.forecast_horizon,
                                          cfg.forecast_hidden), dtype=dtype)
        return params

    # -- shared embedding / head -------------------------------------------
    @staticmethod
    def _embed(params, cfg, tokens, prefix_embeddings):
        h = Embedding.apply(params["embed"], tokens)
        if cfg.embed_scale:
            h = h * jnp.asarray(np.sqrt(cfg.d_model), h.dtype)
        if prefix_embeddings is not None:
            h = jnp.concatenate(
                [prefix_embeddings.astype(h.dtype), h], axis=1)
        return constrain(h, ("batch", "seq", "embed"))

    @staticmethod
    def _head(params, cfg, h):
        if cfg.tie_embeddings:
            logits = Embedding.attend(params["embed"], h)
        else:
            logits = Dense.apply(params["head"], h)
        return constrain(logits, ("batch", "seq", "vocab"))

    # -- full-sequence forward ----------------------------------------------
    @staticmethod
    def apply(params, cfg: ModelConfig, tokens, prefix_embeddings=None,
              moe_capacity=None, remat: bool = False):
        """tokens: (B, S) int. Returns (logits (B, S_tot, V), h, aux).

        ``moe_capacity=None`` = no-drop MoE (exact ARM semantics; inference
        default). Training passes a finite capacity factor. ``remat=True``
        checkpoints each block (activation memory ~ one layer boundary)."""
        h = TransformerLM._embed(params, cfg, tokens, prefix_embeddings)
        aux = jnp.zeros((), jnp.float32)

        def run_block(carry, block_p, specs):
            h, aux = carry
            for p, spec in zip(block_p, specs):
                h, aux = _layer_full(p, spec, cfg, h, aux, moe_capacity)
            return h, aux

        if remat:
            run_block = jax.checkpoint(run_block, static_argnums=(2,))

        h, aux = run_block((h, aux), params["prefix"], cfg.layer_prefix)

        if cfg.n_blocks:
            def body(carry, block_p):
                return run_block(carry, block_p, cfg.layer_block), None

            (h, aux), _ = jax.lax.scan(body, (h, aux), params["blocks"])

        h, aux = run_block((h, aux), params["suffix"], cfg.layer_suffix)

        h = RMSNorm.apply(params["final_norm"], h)
        logits = TransformerLM._head(params, cfg, h)
        return logits, h, aux

    # -- caches ---------------------------------------------------------------
    @staticmethod
    def init_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=None):
        dtype = dtype or cfg.param_dtype
        cache = {
            "prefix": [_layer_cache_init(s, cfg, batch, max_len, dtype)
                       for s in cfg.layer_prefix],
            "suffix": [_layer_cache_init(s, cfg, batch, max_len, dtype)
                       for s in cfg.layer_suffix],
        }
        if cfg.n_blocks:
            one = [_layer_cache_init(s, cfg, batch, max_len, dtype)
                   for s in cfg.layer_block]
            cache["blocks"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (cfg.n_blocks,) + x.shape), one)
        return cache

    # -- verify-window decode -------------------------------------------------
    @staticmethod
    def decode_window(params, cfg: ModelConfig, tokens, cache, cache_len,
                      state_mode: str = "per_position", accept=None,
                      paged: Optional[PagedView] = None):
        """tokens: (B, W) candidates; cache_len: (B,). Returns
        (logits (B, W, V), h, new_cache). See ``_layer_window`` for
        ``state_mode`` (per-position states vs the two-pass C4 modes).
        ``paged`` switches attention leaves to block-pool addressing — use
        ``decode_window_paged`` which also routes the recurrent rows."""
        h = TransformerLM._embed(params, cfg, tokens, None)
        new_cache = {"prefix": [], "suffix": []}

        for p, spec, c in zip(params["prefix"], cfg.layer_prefix,
                              cache["prefix"]):
            h, nc = _layer_window(p, spec, cfg, h, c, cache_len,
                                  state_mode, accept, paged)
            new_cache["prefix"].append(nc)

        if cfg.n_blocks:
            def body(h, xs):
                block_p, block_c = xs
                ncs = []
                for i, spec in enumerate(cfg.layer_block):
                    h, nc = _layer_window(block_p[i], spec, cfg, h,
                                          block_c[i], cache_len,
                                          state_mode, accept, paged)
                    ncs.append(nc)
                return h, ncs

            h, blocks_nc = jax.lax.scan(body, h,
                                        (params["blocks"], cache["blocks"]))
            new_cache["blocks"] = blocks_nc

        for p, spec, c in zip(params["suffix"], cfg.layer_suffix,
                              cache["suffix"]):
            h, nc = _layer_window(p, spec, cfg, h, c, cache_len,
                                  state_mode, accept, paged)
            new_cache["suffix"].append(nc)

        h = RMSNorm.apply(params["final_norm"], h)
        logits = TransformerLM._head(params, cfg, h)
        return logits, h, new_cache

    @staticmethod
    def decode_window_paged(params, cfg: ModelConfig, tokens, paged_cache,
                            view: PagedView, cache_len,
                            state_mode: str = "per_position", accept=None):
        """Verify-window decode straight over the physical block pools — the
        paged-attention hot path. No dense attention K/V view is built and
        no standalone window scatter runs before the kernel: attention
        leaves stay (P, bs, ...) and each layer's single fused pallas_call
        attends through ``view.tables`` while committing its window K/V into
        the physical blocks as an aliased epilogue (gather-view fallback
        with the aliased ``paged_window_write`` per ``view.use_kernel``).
        Recurrent state leaves (un-paged, (B, ...) slot-indexed) are routed
        to the ``view.rows`` being decoded. Returns (logits, h, new_cache)
        where new_cache holds the updated pools for attention leaves and
        per-position states for recurrent leaves — feed it through
        ``select_states`` then ``adopt_states_paged``."""
        cache = TransformerLM._map_paged(
            cfg, (paged_cache,),
            lambda stacked, leaf: leaf,
            lambda stacked, leaf: (leaf[:, view.rows] if stacked
                                   else leaf[view.rows]))
        return TransformerLM.decode_window(params, cfg, tokens, cache,
                                           cache_len, state_mode, accept,
                                           paged=view)

    @staticmethod
    def adopt_states_paged(cfg: ModelConfig, paged_cache, sel, rows):
        """Merge a paged decode's outputs back into the pool pytree:
        attention pool leaves were already updated functionally by the
        per-layer window writes (take them from ``sel``); recurrent leaves
        adopt the selected per-row states at ``rows``."""
        def rec(stacked, pleaf, sleaf):
            if stacked:
                return pleaf.at[:, rows].set(sleaf)
            return pleaf.at[rows].set(sleaf)

        return TransformerLM._map_paged(
            cfg, (paged_cache, sel),
            lambda stacked, pleaf, sleaf: sleaf, rec)

    # -- paged (block-table) cache access ------------------------------------
    #
    # The serving runtime stores attention K/V (and MLA latents) in fixed-size
    # blocks of a shared physical pool instead of dense per-slot buffers:
    # leaf (B, S, ...) becomes (P, block_size, ...) plus a per-sequence block
    # table (B, S / block_size) of physical ids. Physical block 0 is reserved
    # as a write sink for masked scatter lanes and unallocated table entries —
    # its contents are garbage by design and are never read unmasked
    # (DESIGN.md §6). Recurrent mixer states (Mamba/RWKV) are tiny per-slot
    # snapshots, not paged; they stay batch-indexed.

    @staticmethod
    def _map_paged(cfg: ModelConfig, caches, fn_attn, fn_rec):
        """Walk one or more cache-shaped pytrees in lockstep, applying
        ``fn_attn(stacked, *leaves)`` to attention cache leaves and
        ``fn_rec(stacked, *leaves)`` to recurrent state leaves."""
        def per_layer(spec, entries, stacked):
            mixer, ffn = spec
            out = {}
            if mixer in ("attn", "local", "mla"):
                out["mixer"] = jax.tree.map(
                    lambda *ls: fn_attn(stacked, *ls),
                    *[e["mixer"] for e in entries])
            elif mixer in ("mamba", "rwkv"):
                out["mixer"] = jax.tree.map(
                    lambda *ls: fn_rec(stacked, *ls),
                    *[e["mixer"] for e in entries])
            if ffn == "rwkv_cmix":
                out["ffn"] = jax.tree.map(
                    lambda *ls: fn_rec(stacked, *ls),
                    *[e["ffn"] for e in entries])
            return out

        res = {"prefix": [per_layer(s, [c["prefix"][i] for c in caches],
                                    False)
                          for i, s in enumerate(cfg.layer_prefix)],
               "suffix": [per_layer(s, [c["suffix"][i] for c in caches],
                                    False)
                          for i, s in enumerate(cfg.layer_suffix)]}
        if cfg.n_blocks:
            res["blocks"] = [per_layer(s, [c["blocks"][i] for c in caches],
                                       True)
                             for i, s in enumerate(cfg.layer_block)]
        return res

    @staticmethod
    def init_paged_cache(cfg: ModelConfig, batch: int, num_blocks: int,
                         block_size: int, dtype=None):
        """Physical block pool: attention leaves (num_blocks, block_size, ...)
        (scanned segments keep their leading layer axis); recurrent state
        leaves stay (batch, ...) slot-indexed."""
        dtype = dtype or cfg.param_dtype
        tmpl = TransformerLM.init_cache(cfg, batch, block_size, dtype)

        def attn(stacked, leaf):
            if stacked:
                return jnp.zeros((leaf.shape[0], num_blocks)
                                 + leaf.shape[2:], leaf.dtype)
            return jnp.zeros((num_blocks,) + leaf.shape[1:], leaf.dtype)

        return TransformerLM._map_paged(cfg, (tmpl,), attn,
                                        lambda stacked, leaf: leaf)

    @staticmethod
    def paged_partition_specs(cfg: ModelConfig, paged, data_axis="data"):
        """PartitionSpec pytree for a mesh-sharded paged cache: every leaf's
        pool dim (attention: physical blocks) or slot dim (recurrent states:
        batch) is sharded over ``data_axis``; scanned segments keep their
        leading layer axis unsharded. These are the shard_map in/out specs
        of the mesh serving round (DESIGN.md §10) — each data shard owns a
        contiguous sub-pool and its tables hold shard-local block ids, so
        paged indirection never crosses shards."""
        from jax.sharding import PartitionSpec as P

        def spec(stacked, leaf):
            return P(None, data_axis) if stacked else P(data_axis)

        return TransformerLM._map_paged(cfg, (paged,), spec, spec)

    @staticmethod
    def gather_paged(cfg: ModelConfig, paged, tables, rows):
        """Materialize a dense cache view for ``decode_window``.

        tables: (R, nb) physical block ids per view row; rows: (R,) batch
        slots (selects recurrent states). View sequence length is
        ``nb * block_size``; table entries past a sequence's allocation point
        at block 0 — those positions are causally masked, so its garbage
        contents never reach an unmasked lane."""
        def attn(stacked, leaf):
            if stacked:
                g = leaf[:, tables]                    # (L, R, nb, bs, ...)
                return g.reshape((g.shape[0], g.shape[1],
                                  g.shape[2] * g.shape[3]) + g.shape[4:])
            g = leaf[tables]                           # (R, nb, bs, ...)
            return g.reshape((g.shape[0], g.shape[1] * g.shape[2])
                             + g.shape[3:])

        def rec(stacked, leaf):
            return leaf[:, rows] if stacked else leaf[rows]

        return TransformerLM._map_paged(cfg, (paged,), attn, rec)

    @staticmethod
    def scatter_paged(cfg: ModelConfig, paged, dense_new, tables, rows,
                      start, width: int, active,
                      interpret: Optional[bool] = None):
        """Write a dense view's ``[start, start + width)`` positions back into
        the physical pool through the same aliased ``paged_window_write``
        kernel the fused round uses, so donation semantics are uniform: only
        blocks intersecting the written span are touched, the commit happens
        in place on the donated pool (no full-pool scatter temp), and lanes
        of inactive rows (and slots past the span) are routed to the
        reserved sink block 0. Recurrent state leaves are adopted
        unconditionally for every view row (mirrors the dense engine, where
        an inactive row's re-run reproduces its snapshot bit-for-bit).
        ``interpret`` is the writeback kernel's mode (None: compiled on
        TPU only)."""
        from repro.kernels.paged_attention.ops import paged_window_write

        act = active.astype(jnp.int32)

        def span(dleaf):
            # dense view values at [start, start + width): (R, width, ...)
            S = dleaf.shape[1]
            idx = jnp.clip(start[:, None] + jnp.arange(width)[None, :],
                           0, S - 1)
            idx = idx.reshape(idx.shape + (1,) * (dleaf.ndim - 2))
            return jnp.take_along_axis(dleaf, idx, axis=1)

        def attn(stacked, pleaf, dleaf):
            if stacked:
                def body(_, pd):
                    p_l, d_l = pd
                    return None, paged_window_write(p_l, span(d_l), tables,
                                                    start, act, interpret)
                _, out = jax.lax.scan(body, None, (pleaf, dleaf))
                return out
            return paged_window_write(pleaf, span(dleaf), tables, start, act,
                                      interpret)

        def rec(stacked, pleaf, dleaf):
            if stacked:
                return pleaf.at[:, rows].set(dleaf)
            return pleaf.at[rows].set(dleaf)

        return TransformerLM._map_paged(cfg, (paged, dense_new), attn, rec)

    @staticmethod
    def select_states(cfg: ModelConfig, new_cache, accept_idx):
        """Adopt the verify outputs: attention buffers are taken as-is (the
        rewound ``cache_len`` shields stale slots); recurrent per-position
        states are gathered at ``accept_idx - 1`` (B,) — the state after the
        last accepted token."""
        B = accept_idx.shape[0]
        gather = jnp.maximum(accept_idx - 1, 0)

        def per_layer(spec, new, stacked: bool):
            mixer, ffn = spec

            def pick(n):
                # n: (B, W, ...) or, for scanned blocks, (n_blocks, B, W, ...)
                if stacked:
                    return n[:, jnp.arange(B), gather]
                return n[jnp.arange(B), gather]

            out = {}
            if mixer in ("attn", "local", "mla"):
                out["mixer"] = new["mixer"]
            elif mixer in ("mamba", "rwkv"):
                out["mixer"] = jax.tree.map(pick, new["mixer"])
            if ffn == "rwkv_cmix":
                out["ffn"] = jax.tree.map(pick, new["ffn"])
            return out

        sel = {"prefix": [per_layer(s, n, False) for s, n in
                          zip(cfg.layer_prefix, new_cache["prefix"])],
               "suffix": [per_layer(s, n, False) for s, n in
                          zip(cfg.layer_suffix, new_cache["suffix"])]}
        if cfg.n_blocks:
            sel["blocks"] = [per_layer(s, new_cache["blocks"][i], True)
                             for i, s in enumerate(cfg.layer_block)]
        return sel
