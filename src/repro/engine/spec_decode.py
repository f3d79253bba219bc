"""Token-domain predictive sampling with a KV cache — the paper's Algorithm 1
as a serving step (windowed verify), DESIGN.md §3.

Round layout (per sequence): accepted tokens ``x_0..x_{n-1}``; the verify
window feeds ``[x_{n-1}, c_n, .., c_{n+W-2}]`` (W tokens; candidates c are
forecasts). Output slot t is the reparametrized sample for position ``n+t``:
``o_t = argmax(logits_t + eps_{n+t})``. Slot 0 is always valid (conditioned
only on accepted tokens); each further slot is valid while the candidate it
was conditioned on matched. Per round, ``a in [1, W]`` tokens are accepted —
identical tokens to ancestral sampling (W=1), by the paper's exactness
argument, just fewer model calls.

Forecasts: FPI reuses the previous round's outputs past the accept point
(paper §2.3 — zero extra compute); optional learned forecasting heads
(TokenForecast / DeepSeek-MTP correspondence) fill the tail (paper §2.4).

Reparametrization noise is *virtual*: ``eps[b, p] = Gumbel(fold_in(key, b, p))``
is recomputed on demand (never materialized at (L, V) scale) — positions keep
their noise across rounds, which is what makes forecasts exactly verifiable
(paper's key insight; Table 3 ablation).

Per-sequence accept lengths mean each sequence advances at its own rate —
the batched-sampling scheduler the paper left to future work (§4.1 "We leave
the implementation of a scheduling system to future work").
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.analysis.hotpath import hot_path
from repro.core.reparam import reparam_argmax
from repro.models.transformer import PagedView, TransformerLM


def make_eps_fn(key, vocab: int):
    """Deterministic per-(noise stream, position) Gumbel noise function.

    ``eps_fn(seq_ids, positions)`` — ``seq_ids (B,)`` names each row's noise
    stream (a serving engine pins it to the request, so a request keeps its
    stream across slots and batch shapes; a plain sampler uses the row index).
    """
    def eps_fn(seq_ids, positions):
        # seq_ids: (B,); positions: (B, W) absolute token positions
        def one(sid, row):
            kb = jax.random.fold_in(key, sid)
            return jax.vmap(
                lambda p: jax.random.gumbel(jax.random.fold_in(kb, p),
                                            (vocab,)))(row)
        return jax.vmap(one)(seq_ids, positions)
    return eps_fn


class GenState(NamedTuple):
    tokens: jnp.ndarray      # (B, L_max) accepted tokens (prompt + generated)
    n: jnp.ndarray           # (B,) accepted length per sequence
    cand: jnp.ndarray        # (B, W) next verify window (slot0 = last token)
    cache: dict
    rounds: jnp.ndarray      # () total verify rounds (batch-level ARM calls)
    per_seq_calls: jnp.ndarray  # (B,) rounds in which the sequence was active
    accept_hist: jnp.ndarray    # (B,) total accepted tokens while active
    seq_ids: jnp.ndarray        # (B,) noise-stream id per row (see make_eps_fn)


class PredictiveSampler:
    """Batched predictive-sampling text generation for any TransformerLM."""

    def __init__(self, cfg, params, window: int = 8, max_len: int = 256,
                 eps_key=None, use_forecast_heads: bool = False,
                 use_verify_kernel: bool = False):
        self.cfg = cfg
        self.params = params
        self.W = window
        self.max_len = max_len
        self.eps_fn = make_eps_fn(
            eps_key if eps_key is not None else jax.random.PRNGKey(0),
            cfg.vocab)
        self.use_forecast_heads = (use_forecast_heads
                                   and "forecast" in params
                                   and cfg.forecast_horizon > 0)
        # TPU fast path: the fused vocab-tiled Gumbel-argmax Pallas kernel
        # (kernels/spec_verify); interpret-mode on CPU, bit-identical.
        self.use_verify_kernel = use_verify_kernel
        # params are an argument, not a closure: a closed-over model would be
        # baked into the program as constants (gigabytes at published widths)
        self._round = jax.jit(self._round_impl)

    # ------------------------------------------------------------------
    def init_state(self, prompts, batch: int, seq_ids=None) -> GenState:
        """prompts: (B, L_p) int (uniform prompt length for the state init;
        ragged admission is handled by the serving engine). ``seq_ids``
        selects each row's noise stream (default: row index)."""
        cfg, W = self.cfg, self.W
        B, L_p = prompts.shape
        assert L_p >= 1
        cache = TransformerLM.init_cache(cfg, B, self.max_len + W,
                                         dtype=cfg.param_dtype)
        tokens = jnp.zeros((B, self.max_len), jnp.int32)
        tokens = tokens.at[:, :L_p].set(prompts)

        if L_p > 1:
            # prefill the first L_p - 1 tokens (their KV/state enter the cache)
            _, _, cache = TransformerLM.decode_window(
                self.params, cfg, prompts[:, :-1], cache,
                jnp.zeros((B,), jnp.int32))
            cache = TransformerLM.select_states(
                cfg, cache, jnp.full((B,), L_p - 1, jnp.int32))
        n = jnp.full((B,), L_p, jnp.int32)
        cand = jnp.zeros((B, W), jnp.int32)
        cand = cand.at[:, 0].set(prompts[:, -1])
        if seq_ids is None:
            seq_ids = jnp.arange(B, dtype=jnp.int32)
        return GenState(tokens, n, cand, cache,
                        jnp.zeros((), jnp.int32),
                        jnp.zeros((B,), jnp.int32),
                        jnp.zeros((B,), jnp.int32),
                        jnp.asarray(seq_ids, jnp.int32))

    # ------------------------------------------------------------------
    def _round_impl(self, params, state: GenState, target_len) -> GenState:
        state, _stats = verify_round(
            params, self.cfg, self.eps_fn, state, target_len,
            use_forecast_heads=self.use_forecast_heads,
            use_verify_kernel=self.use_verify_kernel)
        return state

    # ------------------------------------------------------------------
    def generate(self, prompts, new_tokens: int, seq_ids=None):
        """Generate ``new_tokens`` per sequence. Returns (tokens, stats).

        ``seq_ids`` pins each row to a noise stream (default: row index) —
        a serving engine replays the same stream to reproduce a request
        bit-for-bit regardless of which batch slot served it."""
        B, L_p = prompts.shape
        target = jnp.full((B,), L_p + new_tokens, jnp.int32)
        assert L_p + new_tokens <= self.max_len
        state = self.init_state(jnp.asarray(prompts, jnp.int32), B,
                                seq_ids=seq_ids)
        while bool(jnp.any(state.n < target)):
            state = self._round(self.params, state, target)
        stats = {
            "rounds": int(state.rounds),
            "per_seq_calls": jax.device_get(state.per_seq_calls),
            "baseline_calls": new_tokens,
            "mean_accept": float(jnp.mean(
                state.accept_hist / jnp.maximum(state.per_seq_calls, 1))),
        }
        return state.tokens, stats


# ---------------------------------------------------------------------------
# The verify round as a pure function (shared by PredictiveSampler and the
# serving engine, which feeds it block-table cache views and variable W)
# ---------------------------------------------------------------------------

@hot_path
@jax.named_scope("verify_round")
def verify_round(params, cfg, eps_fn, state: GenState, target_len,
                 use_forecast_heads: bool = False,
                 use_verify_kernel: bool = False,
                 paged: Optional[PagedView] = None,
                 poison=None,
                 prompt_len=None):
    """One verify round over ``state``. W is taken from
    ``state.cand.shape[1]`` so callers may vary the window round-to-round
    (adaptive speculation): candidates only gate acceptance, never token
    values, so any W yields the same accepted stream (DESIGN.md §3, §7).

    ``state.cache`` is a dense cache view, or — with ``paged`` — the paged
    block-pool pytree, decoded in place through the block tables (no dense
    attention K/V view is ever materialized; DESIGN.md §9).

    ``poison`` (B,) int32, optional, is the serving engine's fault-
    injection seam (DESIGN.md §14): rows with ``poison > 0`` have their
    logits NaN-replaced *post-model*, so K/V written to the cache stay
    finite and row-local — a poisoned row degrades only itself while the
    quarantine health flag (below) trips for it.

    Returns ``(new_state, row_stats)`` where ``row_stats`` is the packed
    (B, 4) int32 per-row stats vector ``[accepted, done, new_length,
    nonfinite]`` — everything a driving loop needs to decide continuation
    and everything a host needs per sync, without pulling
    ``n``/``cand``/``tokens`` (the device-resident round loop ABI,
    DESIGN.md §11). The ``nonfinite`` health column is always computed
    (one cheap ``isfinite`` reduce next to the vocab matmul): any NaN/inf
    in a row's logits — poisoned or genuinely numerically broken — reports
    1 there, the engine's quarantine signal (§14).

    ``prompt_len`` (B,) int32, optional, enables *forced-acceptance
    prefill* (DESIGN.md §15): rows whose accepted length ``n`` is still
    inside their prompt (``n < prompt_len``) carry true prompt tokens in
    their candidate window, so every window slot landing on a prompt
    position is force-matched (the prompt is ground truth — no sampling
    gate applies), token writes preserve the prompt region, and the next
    window is overlaid with prompt tokens wherever it still covers the
    prompt. A row with ``prompt_len <= n`` is bitwise unaffected (every
    forced-match / mask / overlay predicate is False), so resident
    sequences and the ``prompt_len=None`` solo path stay exact."""
    B, W = state.cand.shape
    max_len = state.tokens.shape[1]
    active = state.n < target_len

    cache_len = state.n - 1
    if paged is None:
        logits, h, new_cache = TransformerLM.decode_window(
            params, cfg, state.cand, state.cache, cache_len)
    else:
        logits, h, new_cache = TransformerLM.decode_window_paged(
            params, cfg, state.cand, state.cache, paged, cache_len)
    logits = logits.astype(jnp.float32)
    if poison is not None:
        logits = jnp.where((poison > 0)[:, None, None], jnp.nan, logits)
    nonfinite = 1 - jnp.all(jnp.isfinite(logits),
                            axis=(1, 2)).astype(jnp.int32)
    out_pos = state.n[:, None] + jnp.arange(W)[None, :]   # sampled positions
    with jax.named_scope("noise"):
        eps = eps_fn(state.seq_ids, out_pos)
    if use_verify_kernel:
        from repro.kernels.spec_verify.ops import spec_verify
        out = spec_verify(logits, eps)                    # (B, W)
    else:
        out = reparam_argmax(logits, eps)

    # accept length: slot t+1 valid while candidate c_{n+t} matched o_t
    match = state.cand[:, 1:] == out[:, :-1]               # (B, W-1)
    if prompt_len is not None:
        # forced-acceptance prefill: candidate c_{n+t} at a prompt position
        # is the true prompt token — no gate applies
        forced = (state.n[:, None] + jnp.arange(W - 1)[None, :]) \
            <= (prompt_len[:, None] - 1)
        match = match | forced
    a = 1 + jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    a = jnp.minimum(a, jnp.maximum(target_len - state.n, 1))
    a = jnp.where(active, a, 0)

    # write accepted tokens
    pos = jnp.arange(max_len)[None, :]
    newly = (pos >= state.n[:, None]) & (pos < (state.n + a)[:, None])
    if prompt_len is not None:
        newly = newly & (pos >= prompt_len[:, None])   # preserve the prompt
    slot = jnp.clip(pos - state.n[:, None], 0, W - 1)
    tokens = jnp.where(newly, jnp.take_along_axis(out, slot, axis=1),
                       state.tokens)

    n_new = state.n + a
    # cache: adopt window writes; recurrent states at the accept point.
    # Inactive rows must keep their old recurrent snapshot (a=0 -> the
    # gather would fetch slot -1); clamp handles it because their cand
    # window re-ran from the same snapshot: slot 0 state == snapshot
    # after x_{n-1}... only true if cand[:,0] stayed x_{n-1} — it does.
    sel = TransformerLM.select_states(cfg, new_cache,
                                      jnp.maximum(a, 1))
    if paged is None:
        cache = sel
    else:
        cache = TransformerLM.adopt_states_paged(cfg, state.cache, sel,
                                                 paged.rows)

    # next window: slot0 = last accepted token; FPI forecasts = this
    # round's outputs past the accept point (paper §2.3)
    idx = (a - 1)[:, None] + jnp.arange(W)[None, :]        # (B, W)
    fpi = jnp.take_along_axis(out, jnp.minimum(idx, W - 1), axis=1)
    valid_fpi = idx <= (W - 1)
    cand = jnp.where(valid_fpi, fpi, 0)

    if use_forecast_heads:
        from repro.core.forecasting import (TokenForecast,
                                            TokenForecastConfig)
        fcfg = TokenForecastConfig(cfg.d_model, cfg.vocab,
                                   cfg.forecast_horizon,
                                   cfg.forecast_hidden)
        fc_logits = TokenForecast.apply(params["forecast"], h, fcfg)
        # anchor slot a (uses h[a-1], the last fully-valid slot); offset
        # j forecasts window slot a-1+j -> next-window slot j + ... we
        # fill tail slots where FPI ran out (valid_fpi == False).
        # anchor s=a reads h[a-1] (last fully-valid slot); its offset-t
        # logits forecast window slot a+t... = position n_new-1+t, i.e.
        # next-window slot s' uses offset t = s'.
        anchor = jnp.minimum(a, W - 1)
        fc_a = jnp.take_along_axis(
            fc_logits, anchor[:, None, None, None], axis=1)[:, 0]  # (B,T,V)
        T = cfg.forecast_horizon
        s_idx = jnp.arange(W)
        t_of_s = jnp.clip(s_idx, 0, T - 1)
        with jax.named_scope("noise"):
            eps_next = eps_fn(state.seq_ids,
                              n_new[:, None] - 1 + s_idx[None, :])
        fc_tok = reparam_argmax(
            jnp.take_along_axis(
                fc_a, jnp.broadcast_to(t_of_s[None, :, None],
                                       (B, W, 1)), axis=1),
            eps_next)
        use_fc = (~valid_fpi) & (s_idx[None, :] < T)
        cand = jnp.where(use_fc, fc_tok, cand)

    if prompt_len is not None:
        # next-window slots still inside the prompt must carry the true
        # prompt tokens (they source the K/V writes + the forced matches)
        p = (n_new - 1)[:, None] + jnp.arange(W)[None, :]
        prompt_tok = jnp.take_along_axis(
            tokens, jnp.clip(p, 0, max_len - 1), axis=1)
        cand = jnp.where(p <= prompt_len[:, None] - 1, prompt_tok, cand)

    # slot 0 must be the last accepted token
    last_tok = jnp.take_along_axis(tokens,
                                   jnp.maximum(n_new - 1, 0)[:, None],
                                   axis=1)[:, 0]
    cand = cand.at[:, 0].set(last_tok)
    cand = jnp.where(active[:, None], cand, state.cand)
    n_new = jnp.where(active, n_new, state.n)
    tokens = jnp.where(active[:, None], tokens, state.tokens)

    new_state = GenState(
        tokens, n_new, cand, cache,
        state.rounds + jnp.any(active).astype(jnp.int32),
        state.per_seq_calls + active.astype(jnp.int32),
        state.accept_hist + a,
        state.seq_ids,
    )
    row_stats = jnp.stack(
        [a, (n_new >= target_len).astype(jnp.int32), n_new, nonfinite],
        axis=1)
    return new_state, row_stats
