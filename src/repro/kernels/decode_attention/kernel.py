"""Pallas TPU kernel: flash-decode — W window queries vs a long KV cache.

The predictive-sampling verify step attends W (<=16) fresh queries against a
cache of up to 524,288 keys. Compute is dominated by streaming the cache
through VMEM once (bandwidth-bound, the long_500k roofline term); queries
ride along whole.

grid = (BH, ceil(S/bk)): per (batch*head), KV tiles stream sequentially with
the online-softmax state for all W queries in scratch. Per-sequence valid
length masks tail tiles (cache slots beyond ``length + W`` are never
counted). A ragged final tile is masked *in-kernel* against the true S —
no host-side ``jnp.pad`` copy of the whole cache on the hot path; its
out-of-bounds K/V rows are zeroed before the matmuls so garbage (possibly
non-finite) memory can never poison the accumulator through ``0 * v``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG = -1.0e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, bk: int, s_len: int, scale: float,
                   window: int):
    jk = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], NEG)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    q = q_ref[0].astype(jnp.float32)                     # (W, d)
    k = k_ref[0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    W = q.shape[0]
    # ragged tail tile: rows at k_pos >= S are out-of-bounds reads
    in_bounds = (jk * bk
                 + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)) < s_len
    k = jnp.where(in_bounds, k, 0.0)
    v = jnp.where(in_bounds, v, 0.0)
    s = (q @ k.T) * scale                                # (W, bk)

    base = len_ref[pl.program_id(0)]                     # valid cache length
    q_pos = base + jax.lax.broadcasted_iota(jnp.int32, (W, bk), 0)
    k_pos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (W, bk), 1)
    mask = (k_pos <= q_pos) & (k_pos < s_len)
    if window > 0:
        mask &= k_pos > (q_pos - window)
    s = jnp.where(mask, s, NEG)

    m_prev, l_prev = m_ref[...], l_ref[...]                # (W, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + p @ v
    m_ref[...] = m_new

    @pl.when(jk == nk - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "interpret"))
def decode_attention_kernel(q, k, v, lengths, window: int = 0,
                            block_k: int = 512,
                            interpret: bool | None = None):
    """q: (BH, W, d) window queries; k, v: (BH, S, d) caches (window keys
    already written at positions lengths..lengths+W-1); lengths: (BH,) valid
    prefix lengths. Query w attends keys < lengths + w + 1."""
    BH, W, d = q.shape
    S = k.shape[1]
    bk = min(block_k, S)

    # lengths ride in SMEM via scalar prefetch; the online-softmax state is
    # rank-2 ((W, 1) max/sum) — Mosaic refuses rank-1 SMEM and VMEM blocks
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, -(-S // bk)),
        in_specs=[
            pl.BlockSpec((1, W, d), lambda b, j, ln: (b, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, ln: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, ln: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, W, d), lambda b, j, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((W, 1), jnp.float32),
            pltpu.VMEM((W, 1), jnp.float32),
            pltpu.VMEM((W, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bk=bk, s_len=S,
                          scale=1.0 / d ** 0.5, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, W, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(lengths.astype(jnp.int32), q, k, v)
    return out
