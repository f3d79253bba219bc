"""Pallas TPU kernels: paged flash-decode with a fused window-writeback
epilogue — attend through block tables AND commit the window K/V, in one
dispatch.

The serving runtime stores attention K/V in fixed-size pages (blocks) of a
shared physical pool ``(P, bs, KV, d)`` (``TransformerLM.init_paged_cache``);
each sequence owns a block table mapping logical page ``j`` to a physical
pool id. One pallas_call per layer reads the pool through the tables and
commits the W fresh window rows into their pages (DESIGN.md §9, §11).

**Multi-page compute blocks.** grid = (B, ceil(nb / ppb)): each grid step
scores ``ppb`` consecutive logical pages of one row (``ppb * bs`` keys) —
one online-softmax update over the whole block, so the per-step cost is
paid once per block, not once per 16-token page. The pools stay in HBM
(``memory_space=pltpu.HBM``); the kernel gathers a block's pages itself, one
``make_async_copy`` per page from ``table[b, j]``, into a double-buffered
VMEM scratch ``(2, ppb, bs, KV, d)``: while one block is scored, the next
block with work (the next of this row, or the first of the next row) is in
flight. The block tables and lengths ride in SMEM via scalar prefetch.

**Only the pages a row uses are touched.** A row's keys are bounded by its
own length: pages past ``length + W - 1`` (table entries that point at the
reserved sink block 0) and, under a sliding window, pages wholly below the
earliest visible key are never copied, and a block with no such page does
no work at all — per-call traffic tracks the used pages, not the table
width. Inside a partly used block the pages that were not copied hold
stale pool data (or the zeros the scratch starts with, always finite); the
mask below gives them probability 0.

**Straddle-only commit (the fused epilogue).** The W fresh rows arrive as
small ``(B, W, ...)`` inputs. After a block's pages land, only the pages
that the span ``[length, length + W)`` straddles (at most
``ceil((W - 1) / bs) + 1``: 2 for W = 8, 5 for W = 64) are merged — slot
``t`` of page ``j`` takes ``new[j*bs + t - length]`` (a W-way unrolled
select, bitwise the reference scatter) — and attention then runs over the
merged block. Each merged page is committed to the pool output, aliased
with the pool input, by an async copy that is waited on before the grid
step ends (before its buffer slot can be refilled), inside this same
pallas_call. Window pages are sequence-private and shared prefix pages sit
strictly below every window and are only read, so the in-place commit is
race-free: a row never reads a page another row writes. Interpret mode
initializes aliased outputs from the input arrays, so CPU CI sees the same
semantics.

**Masking.** Query row ``r`` serves window query ``w = r % W`` (G grouped
heads share a kv head); key ``k_pos`` is visible to it iff ``k_pos <=
length + w`` (and ``k_pos > length + w - window``). A masked key gets
probability exactly 0, so a block's update over keys that do not count is
bitwise the identity — the same op sequence as the dense ``decode_attention``
kernel at ``block_k = ppb * bs``.

**Precision.** QK^T takes the pool's operands with an f32 result
(``preferred_element_type``; exact for bf16-valued inputs); the softmax,
its statistics and the P·V product stay in f32.

``latent=True`` is the MLA variant: scores are the sum of two inner
products (absorbed-latent query vs the c_kv pool, rope query vs the shared
rope-key pool) and the value *is* the merged c_kv block — one pool read
serves both matmuls; both latent pools get the straddle-only commit.

``pages_per_block`` picks ``ppb`` from the shapes: enough pages to stream
``BLOCK_BYTES`` of pool per grid step, cut to a VMEM budget that counts the
double buffer, the scores and the f32 accumulator, and to the table width.

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
BLOCK_BYTES = 2 << 20        # pool bytes (all pools) one compute block streams
VMEM_BUDGET = 10 << 20       # VMEM the kernel may take, as pages_per_block
                             # counts it, against Mosaic's 16-MiB scoped
                             # limit; the count runs 1.1-3.4x above the
                             # scoped allocation Mosaic makes for the kernel
                             # (v5e compiles of qwen3, gemma3, internvl2,
                             # musicgen, mistral and MLA shapes, W 8 and 64)


def pages_per_block(*, nb: int, bs: int, KV: int, widths, R: int, dv: int,
                    W: int, itemsize: int) -> int:
    """Pages per compute block for pools of ``widths`` (one per pool) at
    ``KV`` heads, ``R`` query rows per head and value width ``dv``: as many
    as stream ``BLOCK_BYTES`` per grid step, while the block's buffers fit
    ``VMEM_BUDGET`` and no more than the table has (``nb``)."""
    width = sum(-(-w // 128) * 128 for w in widths)      # lane-padded
    page = bs * KV * width * itemsize
    fixed = (2 * KV * R * (width + dv) * itemsize     # queries, output (x2)
             + 2 * W * KV * width * itemsize          # window rows (x2)
             + KV * R * (dv + 2 * 128) * 4)           # acc, max, sum (f32)

    def vmem(n):
        keys = n * bs
        return (fixed + 2 * n * page                  # double-buffered pages
                + 4 * R * keys * 4                    # scores, probabilities
                + keys * max(widths) * 4)             # one head's f32 values

    n = max(1, min(nb, BLOCK_BYTES // page))
    while n > 1 and vmem(n) > VMEM_BUDGET:
        n -= 1
    return n


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


def _paged_kernel(tbl_ref, len_ref, *refs, nq: int, bs: int, ppb: int,
                  scale: float, window: int, W: int, widths, lanes,
                  latent: bool):
    qs = refs[:nq]
    pools = refs[nq:nq + 2]                               # HBM, read
    news = refs[nq + 2:nq + 4]
    o_ref = refs[nq + 4]
    outs = refs[nq + 5:nq + 7]                            # HBM, aliased
    bufs = refs[nq + 7:nq + 9]                            # (2, ppb, bs, KV, d)
    m_ref, l_ref, acc_ref, slot_ref, sem, commit_sem = refs[nq + 9:]
    b = pl.program_id(0)
    i = pl.program_id(1)
    nrows = pl.num_programs(0)
    nblk = pl.num_programs(1)
    nb = tbl_ref.shape[1]
    KV, R = qs[0].shape[1], qs[0].shape[2]
    KV1 = len(bufs[0].shape) == 4                         # pages (bs, d)
    T = ppb * bs                                          # keys per block

    def page_of(ref, pool, page):
        """Page ``page`` of a pool over whole lane tiles (``lanes``)."""
        whole = (slice(None),) * (ref.ndim - 2)
        return ref.at[(page,) + whole + (pl.ds(0, lanes[pool]),)]

    def used_pages(row):
        """First and last page of ``row`` holding a key some query sees."""
        base = len_ref[row]
        last = jnp.minimum((base + W - 1) // bs, nb - 1)
        if window > 0:
            return jnp.maximum(base - window + 1, 0) // bs, last
        return 0, last

    def copy_block(row, blk, slot, start: bool):
        """Start (or wait for) the copies of a block's used pages."""
        lo, hi = used_pages(row)

        def page_copy(p, carry):
            j = blk * ppb + p
            page = tbl_ref[row, jnp.minimum(j, nb - 1)]

            @pl.when((j >= lo) & (j <= hi))
            def _():
                for n, (pool, buf) in enumerate(zip(pools, bufs)):
                    copy = pltpu.make_async_copy(
                        page_of(pool, n, page), buf.at[slot, p], sem.at[slot])
                    copy.start() if start else copy.wait()
            return carry

        jax.lax.fori_loop(0, ppb, page_copy, 0)

    lo, hi = used_pages(b)
    first_blk, last_blk = lo // ppb, hi // ppb

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], NEG)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    @pl.when((b == 0) & (i == first_blk))
    def _start():
        # pages a block does not copy keep whatever the buffer held: start
        # from zeros so that is always finite (0 * v must stay 0)
        def zero(p, carry):
            for buf in bufs:
                for slot in range(2):
                    buf[slot, p] = jnp.zeros(buf.shape[2:], buf.dtype)
            return carry

        jax.lax.fori_loop(0, ppb, zero, 0)
        slot_ref[0] = 0
        copy_block(b, i, 0, start=True)

    @pl.when((i >= first_blk) & (i <= last_blk))
    def _block():
        slot = slot_ref[0]
        # ---- prefetch the next block with work into the other slot ------
        row_done = i == last_blk
        nxt = jnp.minimum(jnp.where(row_done, b + 1, b), nrows - 1)
        nxt_blk = jnp.where(row_done, used_pages(nxt)[0] // ppb, i + 1)

        @pl.when(jnp.logical_not(row_done) | (b + 1 < nrows))
        def _prefetch():
            copy_block(nxt, nxt_blk, 1 - slot, start=True)

        slot_ref[0] = 1 - slot
        copy_block(b, i, slot, start=False)

        # ---- straddle-only commit: merge the window rows into the pages
        # of [base, base + W) that lie in this block, send them home -------
        base = len_ref[b]
        w_last = jnp.minimum((base + W - 1) // bs, nb - 1)

        def commit(start: bool):
            def page_commit(t, carry):
                j = base // bs + t
                p = j - i * ppb
                page = tbl_ref[b, jnp.minimum(j, nb - 1)]

                @pl.when((j <= w_last) & (p >= 0) & (p < ppb))
                def _():
                    for n, (new, buf, out) in enumerate(zip(news, bufs,
                                                            outs)):
                        if start:
                            buf[slot, p] = _merge_window(
                                buf[slot, p], new[0], j * bs - base, True, W)
                        copy = pltpu.make_async_copy(
                            buf.at[slot, p], page_of(out, n, page), commit_sem)
                        copy.start() if start else copy.wait()
                return carry

            # the most pages a W-wide span can straddle
            jax.lax.fori_loop(0, (W + bs - 2) // bs + 1, page_commit, 0)

        commit(start=True)

        # ---- one online-softmax update over the block's ppb * bs keys ----
        q_pos = base + jax.lax.broadcasted_iota(jnp.int32, (R, T), 0) % W
        k_pos = i * T + jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
        mask = k_pos <= q_pos
        if window > 0:
            mask &= k_pos > (q_pos - window)
        v_pool = 0 if latent else 1                       # c_kv doubles as V

        def keys(pool, h):
            buf = bufs[pool]
            blk = buf[slot] if KV1 else buf[slot, :, :, h, :]   # (ppb, bs, d)
            return blk.reshape(T, blk.shape[-1])[:, :widths[pool]]

        def qk(q, k):
            return jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

        for h in range(KV):                               # static unroll
            s = qk(qs[0][0, h], keys(0, h))               # (R, T)
            if latent:
                s += qk(qs[1][0, h], keys(1, h))
            s = jnp.where(mask, s, NEG)

            m_prev, l_prev = m_ref[h], l_ref[h]           # (R, 1)
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            v = keys(v_pool, h).astype(jnp.float32)       # (T, dv)
            acc_ref[h] = acc_ref[h] * alpha + p @ v
            m_ref[h] = m_new

        # the commits read this slot, which the step after next refills
        commit(start=False)

    @pl.when(i == nblk - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _lanes(x, lanes: int):
    """``x`` with its last dim zero-padded to ``lanes``."""
    pad = lanes - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _paged_call(qs, pools, news, tables, lengths, *, W: int, window: int,
                scale: float, ppb: int, latent: bool, interpret: bool):
    """One pallas_call over grid (B, ceil(nb / ppb)) for both variants.
    ``qs``: query operands ``(B, KV, R, d_i)``; ``pools``: the two
    ``(P, bs, KV, d_i)`` pools, gathered by the kernel and committed in
    place; ``news``: their ``(B, W, KV, d_i)`` window rows.

    Mosaic copies a page out of an HBM pool only when the page is whole
    tiles of the pool's layout. A one-head pool is passed as ``(P, bs, d)``
    (a bitcast: XLA lays the size-1 head dim out major). A pool narrower
    than 128 lanes is laid out lane-padded to 128 on the chip, so each page
    copy spans the padded lanes (``pl.ds(0, lanes)``, past the logical
    width but inside the page's tiles) into a lane-padded buffer, and the
    kernel computes on the first ``d_i`` lanes; no pool is copied. The
    interpreter has no tiles: there the pools are zero-padded first."""
    B, KV, R, _ = qs[0].shape
    bs = pools[0].shape[1]
    nb = tables.shape[1]
    shapes = [p.shape for p in pools]
    widths = tuple(s[-1] for s in shapes)
    dv = widths[0] if latent else widths[1]
    if KV == 1:
        pools = [p.reshape(p.shape[:2] + p.shape[3:]) for p in pools]
        news = [n.reshape(n.shape[:2] + n.shape[3:]) for n in news]
    lanes = tuple(-(-w // 128) * 128 for w in widths)
    if interpret:                   # no tiles to copy whole: pad the arrays
        pools = [_lanes(p, n) for p, n in zip(pools, lanes)]
    news = [_lanes(x, n) for x, n in zip(news, lanes)]

    def row(nd):
        return lambda b, i, tbl, ln: (b,) + (0,) * (nd - 1)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, pl.cdiv(nb, ppb)),
        in_specs=([pl.BlockSpec((1,) + q.shape[1:], row(4)) for q in qs]
                  + [hbm for _ in pools]
                  + [pl.BlockSpec((1, W) + n.shape[2:], row(n.ndim))
                     for n in news]),
        out_specs=([pl.BlockSpec((1, KV, R, dv), row(4))]
                   + [hbm for _ in pools]),
        scratch_shapes=(
            [pltpu.VMEM((2, ppb) + p.shape[1:-1] + (n,), p.dtype)
             for p, n in zip(pools, lanes)]
            + [pltpu.VMEM((KV, R, 1), jnp.float32),       # running max
               pltpu.VMEM((KV, R, 1), jnp.float32),       # running sum
               pltpu.VMEM((KV, R, dv), jnp.float32),      # accumulator
               pltpu.SMEM((1,), jnp.int32),               # slot being scored
               pltpu.SemaphoreType.DMA((2,)),             # page loads
               pltpu.SemaphoreType.DMA(())]),             # window commits
    )
    nq = len(qs)
    out, *pools = pl.pallas_call(
        functools.partial(_paged_kernel, nq=nq, bs=bs, ppb=ppb, scale=scale,
                          window=window, W=W, widths=widths, lanes=lanes,
                          latent=latent),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, KV, R, dv), qs[0].dtype)]
                  + [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # flat operands: (tables, lengths, *qs, *pools, *news)
        input_output_aliases={2 + nq: 1, 3 + nq: 2},
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *qs, *pools,
      *news)
    return (out,) + tuple(p[..., :s[-1]].reshape(s)
                          for p, s in zip(pools, shapes))


@functools.partial(jax.jit, static_argnames=("W", "ppb", "window", "scale",
                                             "interpret"))
def paged_decode_kernel(q, k_pool, v_pool, k_new, v_new, tables, lengths, *,
                        W: int, ppb: int, window: int = 0,
                        scale: float | None = None, interpret: bool = True):
    """q: (B, KV, G*W, d) grouped window queries (row = g*W + w); k_pool,
    v_pool: (P, bs, KV, d) physical block pools (window positions stale —
    the kernel commits them); k_new, v_new: (B, W, KV, d) fresh window rows;
    tables: (B, nb) physical block ids; lengths: (B,) valid prefix lengths;
    ppb: pages per compute block (``pages_per_block``). Query w attends keys
    < lengths + w + 1. Returns (out (B, KV, G*W, dv), k_pool, v_pool) with
    the pools updated in place (aliased)."""
    if scale is None:
        scale = 1.0 / q.shape[-1] ** 0.5
    return _paged_call((q,), (k_pool, v_pool), (k_new, v_new), tables,
                       lengths, W=W, window=window, scale=scale, ppb=ppb,
                       latent=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("W", "ppb", "scale",
                                             "interpret"))
def paged_latent_kernel(q_lat, q_rope, c_pool, kr_pool, c_new, kr_new,
                        tables, lengths, *, W: int, ppb: int, scale: float,
                        interpret: bool = True):
    """MLA absorbed-latent variant: q_lat: (B, 1, H*W, r); q_rope:
    (B, 1, H*W, dr); c_pool: (P, bs, 1, r); kr_pool: (P, bs, 1, dr); c_new,
    kr_new: (B, W, 1, r/dr) fresh window latents. Scores sum both inner
    products; the output is the attention-weighted *latent* (B, 1, H*W, r) —
    the merged c_kv block doubles as the value. Returns (out, c_pool,
    kr_pool) with both latent pools committed in place (aliased)."""
    return _paged_call((q_lat, q_rope), (c_pool, kr_pool), (c_new, kr_new),
                       tables, lengths, W=W, window=0, scale=scale, ppb=ppb,
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
