"""Pallas TPU kernels: paged flash-decode with a fused window-writeback
epilogue — attend through block tables AND commit the window K/V, in one
dispatch.

The serving runtime stores attention K/V in fixed-size blocks of a shared
physical pool (``TransformerLM.init_paged_cache``); each sequence owns a
block table mapping logical block ``j`` to a physical pool id. PR 2 made the
verify round attend *through* the tables; it still paid a standalone O(B*W)
``write_window_paged`` scatter before each pallas_call to land the W fresh
window keys/values in their blocks. This kernel fuses that write into the
kernel itself, so one pallas_call per layer both reads the pool and commits
the window (DESIGN.md §11):

grid = (B, nb): per sequence, logical KV blocks stream sequentially. Each
pool tile is a whole block ``(1, bs, KV, d)`` across the kv heads — Mosaic
requires a block's last two dims to be divisible by (8, 128) or equal to the
array's, which a one-head ``(1, bs, 1, d)`` tile is not — and the kernel walks
the kv heads in a static loop. The per-sequence block table and valid
lengths ride in SMEM via scalar prefetch, so the K/V BlockSpec index_map
resolves ``table[b, j]`` before each tile's DMA — the pool is read once,
block-granular, and no dense view ever exists. Online-softmax state for all
KV*G*W rows (G grouped query heads x W window queries per kv head) lives in
VMEM scratch, exactly like the dense ``decode_attention`` kernel.

Fused writeback (the epilogue):

* The W fresh K/V rows arrive as small ``(B, W, ...)`` inputs instead of
  being pre-scattered into the pool. Each tile is **merged** on the fly:
  slot ``t`` of block ``j`` takes ``new[j*bs + t - length]`` when its
  logical position falls in ``[length, length + W)`` and the pool value
  otherwise (a W-way unrolled select — bitwise equal to the gather the
  scatter used to do). Attention runs over the merged tile.
* The pools are **outputs input/output-aliased with the pool inputs**: the
  out BlockSpec index_map routes window-straddling tiles to their physical
  block (``table[b, j]``) and every other tile to the reserved sink block 0,
  so per-round pool *writes* stay O(B*W) — only the straddle blocks (and
  cheap sink dumps) are flushed, and every unvisited block keeps its
  contents through the aliasing. Interpret mode initializes aliased outputs
  from the input arrays, so CPU CI sees identical semantics.
* Each sequence visits each logical block once and window blocks are
  sequence-private (shared prefix blocks always sit strictly below the
  window span), so the only physical block written by more than one grid
  step is the sink — whose contents are garbage by design. That makes the
  in-place aliasing race-free on TPU.

Masking handles the two paged-specific hazards:

* **Tail blocks** — table entries past a sequence's allocation point at the
  reserved sink block 0; their *logical* positions ``j*bs + t`` exceed
  ``length + W - 1`` so the causal mask ``k_pos <= q_pos`` zeroes them (the
  pool is always initialized/written memory — no NaN risk, unlike the dense
  kernel's out-of-bounds tail tiles).
* **Window keys** — merged from the ``new`` operands as above; query w sees
  keys ``<= length + w`` through the same table indirection as the prefix.

``latent=True`` is the MLA variant: scores are the sum of two inner products
(absorbed-latent query vs the c_kv pool, rope query vs the shared rope-key
pool) and the value *is* the merged c_kv tile — one pool read serves both
matmuls; both latent pools get the fused writeback.

``paged_write_kernel`` is the writeback epilogue alone — grid (B, T) over
just the blocks a W-wide span can straddle — used by the CPU-exact gather
fallback and the legacy dense round's ``scatter_paged`` so every pool write
path shares the same aliased, in-place commit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1.0e30


def _merge_window(tile, new_rows, first, valid, W: int):
    """Select window rows into a pool tile ``(bs, ...)``: slot t takes
    ``new_rows[first + t]`` where ``0 <= first + t < W`` (and ``valid``),
    else keeps ``tile[t]``. Unrolled W-way select — bitwise equal to the
    reference scatter, and lowers to plain vector selects on TPU (no dynamic
    gather)."""
    off = first + jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    merged = tile
    for w in range(W):
        take = (off == w) & valid
        merged = jnp.where(take, new_rows[w][None], merged)
    return merged


def _paged_kernel(tbl_ref, len_ref, *refs, bs: int, scale: float,
                  window: int, W: int, latent: bool):
    if latent:
        (q1_ref, q2_ref, k1_ref, k2_ref, n1_ref, n2_ref,
         o_ref, ok1_ref, ok2_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q1_ref, k1_ref, v_ref, n1_ref, n2_ref,
         o_ref, ok1_ref, ok2_ref, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    KV = q1_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], NEG)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    base = len_ref[b]                                     # valid cache length

    # ---- fused window-writeback epilogue -------------------------------
    # Merge the W fresh rows into this tile (all kv heads at once) at their
    # in-block offsets and write the merged tile to the aliased pool
    # outputs. The out index_map routes non-straddling tiles to the sink, so
    # only the O(W) window blocks are really committed; writing
    # unconditionally keeps the out VMEM buffer coherent with whatever block
    # the emission targets. The attention below reads the merged tiles back
    # from these output buffers, one kv head at a time.
    first = j * bs - base
    ok1_ref[0] = _merge_window(k1_ref[0], n1_ref[0], first, True, W)
    if latent:
        ok2_ref[0] = _merge_window(k2_ref[0], n2_ref[0], first, True, W)
        v_out = ok1_ref                                   # c_kv doubles as V
    else:
        ok2_ref[0] = _merge_window(v_ref[0], n2_ref[0], first, True, W)
        v_out = ok2_ref

    # skip fully-masked tiles outright: tail tiles past the last query
    # position (sink-aliased table entries) and, under a sliding window,
    # tiles wholly below the earliest visible key. A skipped tile's update
    # is the identity (p = 0, alpha = 1), so skipping is bitwise-neutral —
    # per-round compute tracks the *used* blocks, not the table width.
    visible = j * bs <= base + W - 1
    if window > 0:
        visible &= (j + 1) * bs > base - window + 1

    @pl.when(visible)
    def _tile():
        # row r serves window query w = r % W (G heads share a kv head)
        R = q1_ref.shape[2]                               # G*W
        q_pos = base + jax.lax.broadcasted_iota(jnp.int32, (R, bs), 0) % W
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (R, bs), 1)
        mask = k_pos <= q_pos
        if window > 0:
            mask &= k_pos > (q_pos - window)

        for h in range(KV):                               # static unroll
            q = q1_ref[0, h].astype(jnp.float32)          # (R, dk)
            k = ok1_ref[0, :, h, :].astype(jnp.float32)   # (bs, dk)
            s = (q @ k.T) * scale                         # (R, bs)
            if latent:
                q2 = q2_ref[0, h].astype(jnp.float32)     # (R, dr)
                k2 = ok2_ref[0, :, h, :].astype(jnp.float32)   # (bs, dr)
                s += (q2 @ k2.T) * scale
            s = jnp.where(mask, s, NEG)

            m_prev, l_prev = m_ref[h], l_ref[h]           # (R, 1)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            v = v_out[0, :, h, :].astype(jnp.float32)     # (bs, dv)
            acc_ref[h] = acc_ref[h] * alpha + p @ v
            m_ref[h] = m_new

    @pl.when(j == nj - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _pool_out_map(bs: int, W: int):
    """Out index_map for an aliased pool output: window-straddling tiles go
    to their physical block, everything else to the reserved sink 0 (whose
    contents are garbage by design) — pool writes stay O(B*W) per round."""
    def index_map(b, j, tbl, ln):
        base = ln[b]
        straddle = (j * bs <= base + W - 1) & ((j + 1) * bs > base)
        return (jnp.where(straddle, tbl[b, j], 0), 0, 0, 0)
    return index_map


def _paged_call(qs, pools, news, tables, lengths, *, W: int, window: int,
                scale: float, latent: bool, interpret: bool):
    """One pallas_call over grid (B, nb) for both variants. ``qs``: query
    operands ``(B, KV, R, d_i)``; ``pools``: the two ``(P, bs, KV, d_i)``
    pools, committed in place; ``news``: their ``(B, W, KV, d_i)`` window
    rows. Every pool and window block spans all KV heads, so its last two
    dims equal the array's (Mosaic's tiling rule holds for any KV)."""
    B, KV, R, _ = qs[0].shape
    bs = pools[0].shape[1]
    nb = tables.shape[1]
    dv = pools[0].shape[-1] if latent else pools[1].shape[-1]

    def row(b, j, tbl, ln):
        return (b, 0, 0, 0)

    def blk(b, j, tbl, ln):
        return (tbl[b, j], 0, 0, 0)

    pool_map = _pool_out_map(bs, W)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=([pl.BlockSpec((1,) + q.shape[1:], row) for q in qs]
                  + [pl.BlockSpec((1, bs) + p.shape[2:], blk) for p in pools]
                  + [pl.BlockSpec((1, W) + n.shape[2:], row) for n in news]),
        out_specs=([pl.BlockSpec((1, KV, R, dv), row)]
                   + [pl.BlockSpec((1, bs) + p.shape[2:], pool_map)
                      for p in pools]),
        scratch_shapes=[
            pltpu.VMEM((KV, R, 1), jnp.float32),          # running max
            pltpu.VMEM((KV, R, 1), jnp.float32),          # running sum
            pltpu.VMEM((KV, R, dv), jnp.float32),         # accumulator
        ],
    )
    nq = len(qs)
    return pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, scale=scale, window=window,
                          W=W, latent=latent),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, R, dv), qs[0].dtype)]
                  + [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # flat operands: (tables, lengths, *qs, *pools, *news)
        input_output_aliases={2 + nq: 1, 3 + nq: 2},
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *qs, *pools,
      *news)


@functools.partial(jax.jit, static_argnames=("W", "window", "scale",
                                             "interpret"))
def paged_decode_kernel(q, k_pool, v_pool, k_new, v_new, tables, lengths, *,
                        W: int, window: int = 0, scale: float | None = None,
                        interpret: bool = True):
    """q: (B, KV, G*W, d) grouped window queries (row = g*W + w); k_pool,
    v_pool: (P, bs, KV, d) physical block pools (window positions stale —
    the kernel commits them); k_new, v_new: (B, W, KV, d) fresh window rows;
    tables: (B, nb) physical block ids; lengths: (B,) valid prefix lengths.
    Query w attends keys < lengths + w + 1. Returns (out (B, KV, G*W, dv),
    k_pool, v_pool) with the pools updated in place (aliased)."""
    if scale is None:
        scale = 1.0 / q.shape[-1] ** 0.5
    return _paged_call((q,), (k_pool, v_pool), (k_new, v_new), tables,
                       lengths, W=W, window=window, scale=scale,
                       latent=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("W", "scale", "interpret"))
def paged_latent_kernel(q_lat, q_rope, c_pool, kr_pool, c_new, kr_new,
                        tables, lengths, *, W: int, scale: float,
                        interpret: bool = True):
    """MLA absorbed-latent variant: q_lat: (B, 1, H*W, r); q_rope:
    (B, 1, H*W, dr); c_pool: (P, bs, 1, r); kr_pool: (P, bs, 1, dr); c_new,
    kr_new: (B, W, 1, r/dr) fresh window latents. Scores sum both inner
    products; the output is the attention-weighted *latent* (B, 1, H*W, r) —
    the merged c_kv tile doubles as the value. Returns (out, c_pool,
    kr_pool) with both latent pools committed in place (aliased)."""
    return _paged_call((q_lat, q_rope), (c_pool, kr_pool), (c_new, kr_new),
                       tables, lengths, W=W, window=0, scale=scale,
                       latent=True, interpret=interpret)


# ---------------------------------------------------------------------------
# Standalone aliased writeback: the epilogue without the attention
# ---------------------------------------------------------------------------

def _write_kernel_body(tbl_ref, st_ref, act_ref, pool_ref, new_ref, out_ref,
                       *, bs: int, W: int, nb: int):
    b = pl.program_id(0)
    t = pl.program_id(1)
    start = st_ref[b]
    blk = start // bs + t
    last = (start + W - 1) // bs
    valid = (blk < nb) & (blk <= last) & (act_ref[b] > 0)
    out_ref[0] = _merge_window(pool_ref[0], new_ref[0], blk * bs - start,
                               valid, W)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_write_kernel(pool, new, tables, start, active, *,
                       interpret: bool = True):
    """Aliased window writeback: commit ``new (B, W, ...)`` into the pool
    ``(P, bs, ...)`` at per-sequence offsets ``start (B,)`` resolved through
    ``tables (B, nb)``. grid = (B, T) visits only the T blocks a W-wide span
    can straddle; the pool is input/output-aliased so unvisited blocks keep
    their contents and the commit happens in place (no full-pool temp on the
    donated buffer). Rows with ``active == 0`` (and out-of-table slots) are
    routed to the reserved sink block 0 where the write degenerates to a
    value-preserving self-copy."""
    P, bs = pool.shape[:2]
    B, W = new.shape[:2]
    nb = tables.shape[1]
    T = (W + bs - 2) // bs + 1          # max blocks a W-wide span straddles
    trail = pool.shape[2:]
    nd = len(trail)

    def pool_map(b, t, tbl, st, act):
        blk = st[b] // bs + t
        last = (st[b] + W - 1) // bs
        valid = (blk < nb) & (blk <= last) & (act[b] > 0)
        phys = jnp.where(valid, tbl[b, jnp.clip(blk, 0, nb - 1)], 0)
        return (phys,) + (0,) * (nd + 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, bs) + trail, pool_map),
            pl.BlockSpec((1, W) + trail,
                         lambda b, t, tbl, st, act: (b,) + (0,) * (nd + 1)),
        ],
        out_specs=pl.BlockSpec((1, bs) + trail, pool_map),
    )
    return pl.pallas_call(
        functools.partial(_write_kernel_body, bs=bs, W=W, nb=nb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # flat operands: (tables, start, active, pool, new)
        input_output_aliases={3: 0},
        interpret=interpret,
    )(tables.astype(jnp.int32), start.astype(jnp.int32),
      active.astype(jnp.int32), pool, new)
