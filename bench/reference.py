"""Plain float32 reference of a dense GQA decoder (Qwen3, Mistral), and the
comparison that decides whether the served tokens are correct.

It follows the published description and imports nothing of the serving
program: token embedding; per layer RMSNorm, Q/K/V projections, per-head
RMSNorm of q and k where the configuration has ``qk_norm`` (Qwen3), rotary
embeddings in the split-half convention with ``rope_theta``, causal softmax
attention with scale ``1/sqrt(head_dim)`` over grouped K/V heads, output
projection, residual; RMSNorm, SwiGLU MLP ``down(silu(gate x) * up x)``,
residual; final RMSNorm; tied or untied head. RMSNorm uses the
configuration's ``rms_norm_eps``. Every matmul runs at
``Precision.HIGHEST`` (float32 on a TPU), layer by layer, with the layer's
weights drawn again from the seed, and attention in blocks of queries, so
that a whole sequence of several thousand positions fits beside nothing but
one layer's weights.

A served token ``t`` at position ``p`` is correct when it is the argmax of
the reference's logits at ``p`` plus the run's noise at ``p``: its *gap* is
``max_v(logit_v + eps_v) - (logit_t + eps_t)``, 0 where the reference agrees
and a small positive number where the served model's rounding flipped a
near tie. ``control=True`` puts the same forward in the program's place at
fp8 (e4m3; weights scaled per output channel and matmul inputs per row),
the step below bfloat16, and reads the gap of the token it ranks first.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import model

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512            # query rows per attention block
ROW_BLOCK = 256          # logit rows per head block


FP8_MAX = 448.0          # largest finite float8_e4m3fn


def _q8(x, axis):
    """fp8 (e4m3) rounding, scaled along ``axis`` so that the largest
    magnitude maps to ``FP8_MAX`` (values, as float32)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, control: bool):
    """x @ w at HIGHEST; with ``control`` both inputs are fp8 first."""
    if control:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (n, S, heads, hd); split-half rotary embedding."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd // 2, dtype=jnp.float32)
                           / (hd // 2)))
    ang = pos[..., None].astype(jnp.float32) * inv          # (n, S, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA attention. q: (n, S, H, hd); k, v: (n, S, KV, hd); S a
    multiple of ``Q_BLOCK``. Queries go in blocks; keys stay whole."""
    n, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qb = q.reshape(n, S // Q_BLOCK, Q_BLOCK, KV, G, hd)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args                                # qi: (n, QB, KV, G, hd)
        s = jnp.einsum("nqkgd,nskd->nkgqs", qi, k,
                       precision=HI) / math.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nkgqs,nskd->nqkgd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(S // Q_BLOCK),
                              jnp.moveaxis(qb, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(n, S, H, hd)


def _layer(cfg, w, h, control: bool):
    n, S, d = h.shape
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.broadcast_to(jnp.arange(S), (n, S))
    x = _rms(h, w["attn_norm"], eps)
    q = _mm(x, w["wq"], control).reshape(n, S, H, hd)
    k = _mm(x, w["wk"], control).reshape(n, S, KV, hd)
    v = _mm(x, w["wv"], control).reshape(n, S, KV, hd)
    if "q_norm" in w:
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    a = _attention(q, k, v).reshape(n, S, H * hd)
    h = h + _mm(a, w["wo"], control)
    x = _rms(h, w["mlp_norm"], eps)
    g = jax.nn.silu(_mm(x, w["w_gate"], control))
    return h + _mm(g * _mm(x, w["w_up"], control), w["w_down"], control)


class Reference:
    """The reference forward of one configuration under one seed's weights.
    Programs are built once per instance and shape."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg = cfg
        self.key = model.weights_key(seed)
        dt = jnp.dtype(cfg["torch_dtype"])
        f32 = jnp.float32

        def weights(key, layer):
            # the served dtype first, so the values are the served ones
            return {n: x.astype(f32) for n, x in
                    model.layer_leaves(key, cfg, layer, dt).items()}

        def top(key):
            out = {"embed": model.leaf(key, cfg, "embed", 0, dt).astype(f32),
                   "final_norm": model.leaf(key, cfg, "final_norm", 0,
                                            dt).astype(f32)}
            out["head"] = (out["embed"].T if cfg["tie_word_embeddings"]
                           else model.leaf(key, cfg, "head", 0,
                                           dt).astype(f32))
            return out

        self._weights = jax.jit(weights)
        self._top = jax.jit(top)
        self._layer = jax.jit(lambda w, h, c: _layer(cfg, w, h, c),
                              static_argnums=2)

    def hidden(self, tokens, control: bool = False):
        """Final-normed hidden states ``(n, S, d)`` of ``tokens (n, S)``,
        ``S`` a multiple of ``Q_BLOCK``. Each layer's weights are drawn
        once; the sequences go through it one at a time, so that every run
        of a cell reuses one compiled layer."""
        top = self._top(self.key)
        hs = [top["embed"][jnp.asarray(t)[None]] for t in np.asarray(tokens)]
        if control:
            hs = [_q8(h, -1) for h in hs]
        for layer in range(self.cfg["num_hidden_layers"]):
            w = self._weights(self.key, layer)
            hs = [self._layer(w, h, control) for h in hs]
        h = jnp.concatenate(hs)
        return _rms(h, top["final_norm"], self.cfg["rms_norm_eps"]), top

    def logits(self, tokens, positions):
        """Reference logits that predict ``tokens[i, p]`` for each
        ``(i, p)`` in ``positions``: ``(len(positions), V)`` float32."""
        h, top = self.hidden(_pad(tokens))
        rows = jnp.stack([h[i, p - 1] for i, p in positions])
        return jnp.dot(rows, top["head"], precision=HI)


def _pad(tokens):
    tokens = np.asarray(tokens)
    S = -(-tokens.shape[1] // Q_BLOCK) * Q_BLOCK
    return np.pad(tokens, ((0, 0), (0, S - tokens.shape[1])))


@jax.jit
def _gap(ref, eps, tok, other):
    """Per row: best perturbed reference logit minus that of ``tok``, and
    minus that of the argmax of ``other + eps``."""
    pert = ref + eps
    best = jnp.max(pert, -1)
    own = jnp.take_along_axis(pert, tok[:, None], -1)[:, 0]
    alt = jnp.take_along_axis(
        pert, jnp.argmax(other + eps, -1)[:, None], -1)[:, 0]
    return best - own, best - alt


def served_gaps(cfg: dict, seed: int, eps_fn, seqs, pad_to: int,
                control: bool = False):
    """Gaps of served tokens against the reference.

    ``seqs``: ``(tokens, prompt_len, noise_stream)`` of each checked request,
    its whole served sequence. All are run together at length ``pad_to``
    (the cell's ``max_len``, so that every run of a cell reuses one
    compiled layer). Returns ``(gaps, control_gaps)``: one float32 array per
    sequence over its served positions; ``control_gaps`` are the gaps of the
    tokens the fp8 forward ranks first (None unless ``control``)."""
    ref = Reference(cfg, seed)
    toks = np.zeros((len(seqs), -(-pad_to // Q_BLOCK) * Q_BLOCK), np.int32)
    for i, (t, _, _) in enumerate(seqs):
        toks[i, :len(t)] = t
    h, top = ref.hidden(toks)
    hc = ref.hidden(toks, control=True)[0] if control else None
    rows = [(i, p) for i, (t, L, _) in enumerate(seqs)
            for p in range(L, len(t))]
    out, ctl = [], []
    for lo in range(0, len(rows), ROW_BLOCK):
        blk = rows[lo:lo + ROW_BLOCK]
        pad = ROW_BLOCK - len(blk)
        blk_p = blk + [blk[-1]] * pad             # one shape for every block
        ii = np.array([i for i, _ in blk_p])
        pp = np.array([p for _, p in blk_p])
        logit = jnp.dot(h[ii, pp - 1], top["head"], precision=HI)
        other = (jnp.dot(_q8(hc[ii, pp - 1], -1), _q8(top["head"], 0),
                         precision=HI) if control else logit)
        eps = eps_fn(jnp.asarray([seqs[i][2] for i in ii], jnp.int32),
                     jnp.asarray(pp[:, None], jnp.int32))[:, 0]
        tok = jnp.asarray([seqs[i][0][p] for i, p in blk_p], jnp.int32)
        g, c = _gap(logit, eps, tok, other)
        out.append(np.asarray(g)[:len(blk)])
        ctl.append(np.asarray(c)[:len(blk)])
    g_all = np.concatenate(out) if out else np.zeros(0, np.float32)
    c_all = np.concatenate(ctl) if ctl else np.zeros(0, np.float32)
    split = np.cumsum([len(t) - L for t, L, _ in seqs])[:-1]
    return (np.split(g_all, split),
            np.split(c_all, split) if control else None)
