"""Jit'd public paged-attention ops (GQA row grouping, MLA latent variant)
with the fused window-writeback epilogue.

Unlike the dense ``decode_attention`` wrapper, GQA is handled by *grouping*
query heads onto their kv head (row = g*W + w) instead of ``jnp.repeat`` on
the cache — the pool is never expanded or copied. Every op takes the W
fresh window rows as separate small operands and returns the updated pools
next to the attention output: the kernel streams each row's used pages
through its table in multi-page compute blocks and commits the window rows
into the pages they straddle, in place (one dispatch — no standalone
scatter before the pallas_call); the ref composes the reference scatter
with the gathered dense view (the CPU oracle shape).

The pages per compute block (``ppb``) are not an option: each op derives
them from its shapes with ``kernel.pages_per_block`` — a fixed byte target
per grid step, cut to the kernel's VMEM budget and the table width — so a
one-head page (KV = 1) takes more pages per block and MLA's wide query
blocks fewer.

``paged_window_write`` is the writeback alone — the same aliased, in-place
commit used by the CPU-exact gather fallback and the legacy dense round's
``scatter_paged``, so *every* pool write path shares one implementation and
one donation story.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.paged_attention.kernel import (pages_per_block,
                                                 paged_decode_kernel,
                                                 paged_latent_kernel,
                                                 paged_write_kernel)
from repro.kernels.paged_attention.ref import (paged_attention_fused_ref,
                                              paged_latent_fused_ref)


def paged_attention(q, k_pool, v_pool, k_new, v_new, tables, lengths,
                    window: int = 0, use_kernel: bool = True,
                    interpret: bool | None = None):
    """q: (B, W, H, d) window queries; k_pool/v_pool: (P, bs, KV, d) physical
    block pools (window positions stale — committed here); k_new/v_new:
    (B, W, KV, d) fresh window rows; tables: (B, nb); lengths: (B,).
    Returns (out (B, W, H, d), k_pool, v_pool) with the window rows written
    through the tables (fused kernel epilogue, or the reference scatter on
    the ref path)."""
    B, W, H, d = q.shape
    KV = k_pool.shape[2]
    G = H // KV
    if not use_kernel:
        return paged_attention_fused_ref(q, k_pool, v_pool, k_new, v_new,
                                         tables, lengths, window=window)
    qg = (q.reshape(B, W, KV, G, d)
          .transpose(0, 2, 3, 1, 4)          # (B, KV, G, W, d): row = g*W + w
          .reshape(B, KV, G * W, d))
    ppb = pages_per_block(nb=tables.shape[1], bs=k_pool.shape[1], KV=KV,
                          widths=(d, v_pool.shape[-1]), R=G * W,
                          dv=v_pool.shape[-1], W=W,
                          itemsize=k_pool.dtype.itemsize)
    out, k_pool, v_pool = paged_decode_kernel(
        qg, k_pool, v_pool, k_new, v_new, tables, lengths, W=W, ppb=ppb,
        window=window, interpret=resolve_interpret(interpret))
    out = (out.reshape(B, KV, G, W, d)
           .transpose(0, 3, 1, 2, 4)
           .reshape(B, W, H, d))
    return out, k_pool, v_pool


def paged_latent_attention(q_lat, q_rope, c_pool, kr_pool, c_new, kr_new,
                           tables, lengths, scale: float,
                           use_kernel: bool = True,
                           interpret: bool | None = None):
    """MLA absorbed-matrix decode over the latent pools. q_lat: (B, W, H, r);
    q_rope: (B, W, H, dr); c_pool: (P, bs, r); kr_pool: (P, bs, dr); c_new:
    (B, W, r); kr_new: (B, W, dr) fresh window latents. Returns (ctx
    (B, W, H, r), c_pool, kr_pool) — the attention-weighted latent (the
    caller applies W_uv/W_o) plus both pools with the window committed."""
    B, W, H, r = q_lat.shape
    dr = q_rope.shape[-1]
    if not use_kernel:
        return paged_latent_fused_ref(q_lat, q_rope, c_pool, kr_pool,
                                      c_new, kr_new, tables, lengths,
                                      scale=scale)
    # all H heads share the single latent "kv head": rows = h*W + w
    ql = q_lat.transpose(0, 2, 1, 3).reshape(B, 1, H * W, r)
    qr = q_rope.transpose(0, 2, 1, 3).reshape(B, 1, H * W, dr)
    ppb = pages_per_block(nb=tables.shape[1], bs=c_pool.shape[1], KV=1,
                          widths=(r, dr), R=H * W, dv=r, W=W,
                          itemsize=c_pool.dtype.itemsize)
    out, c4, kr4 = paged_latent_kernel(
        ql, qr, c_pool[:, :, None, :], kr_pool[:, :, None, :],
        c_new[:, :, None, :], kr_new[:, :, None, :], tables, lengths,
        W=W, ppb=ppb, scale=scale, interpret=resolve_interpret(interpret))
    out = out.reshape(B, H, W, r).transpose(0, 2, 1, 3)
    return out, c4[:, :, 0, :], kr4[:, :, 0, :]


def paged_window_write(pool, new, tables, start, active=None,
                       interpret: bool | None = None):
    """Standalone aliased window writeback (the fused epilogue without the
    attention): commit ``new (B, W, ...)`` into ``pool (P, bs, ...)`` at
    offsets ``start (B,)`` through ``tables (B, nb)``, in place. Rows with
    ``active == False`` are routed to the reserved sink block 0. Used by the
    CPU-exact gather fallback and the legacy dense round's scatter so
    donation semantics are uniform across every pool write path."""
    if active is None:
        active = jnp.ones(new.shape[:1], jnp.int32)
    return paged_write_kernel(pool, new, tables, start, active,
                              interpret=resolve_interpret(interpret))
