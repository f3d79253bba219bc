"""Paged flash-decode kernel (fused window-writeback epilogue) vs its
oracle, the dense decode kernel, and the dense decode reference (interpret
mode).

The load-bearing invariants:
* fused kernel == fused ref (reference ``write_window_paged`` scatter +
  gather view + plain softmax) across block sizes, ragged lengths with
  partially filled tail blocks, and W in {1, 4, 16} — on the attention
  output AND bitwise on the committed pools (excluding the reserved sink
  block 0, whose contents are garbage by design);
* the same with several pages per compute block (``ppb``): lengths ending
  inside, at the start and at the end of a block, window spans straddling
  two blocks, a 64-wide (prefill) window over 5 pages, rows far shorter
  than the table, shared prefix pages, a sliding window that skips whole
  blocks, and the latent variant;
* with the dense tile equal to the compute block (``ppb * bs``, as
  ``pages_per_block`` picks it) the fused kernel is BITWISE identical to
  the dense ``decode_attention_kernel`` run over the post-write gathered
  view — the same online-softmax op sequence, only the addressing (and the
  fused commit) differs;
* the standalone aliased writeback (``paged_window_write``) is bitwise
  identical to the reference scatter, including inactive-row sink routing;
* block tables with shared prefix blocks (prefix-cache hits) read the same
  physical memory from both sequences and the epilogue never writes them;
* table entries past the allocation point (sink block 0) never contribute.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import decode_attention_kernel
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.paged_attention.kernel import (pages_per_block,
                                                  paged_decode_kernel,
                                                  paged_latent_kernel)
from repro.kernels.paged_attention.ops import (paged_attention,
                                               paged_latent_attention,
                                               paged_window_write)
from repro.kernels.paged_attention.ref import (gather_view,
                                              paged_attention_ref,
                                              paged_latent_ref,
                                              write_window_paged)


def _pool_and_tables(key, P, bs, nb, KV, d, B, dtype=jnp.float32,
                     shared_prefix=0):
    """Random pools plus per-sequence tables over distinct physical blocks;
    the first ``shared_prefix`` logical blocks alias the same physical
    blocks across all sequences (prefix-cache shape). Remaining table slots
    past each row's allocation stay 0 (the sink block)."""
    kk, kv = jax.random.split(key)
    k_pool = jax.random.normal(kk, (P, bs, KV, d)).astype(dtype)
    v_pool = jax.random.normal(kv, (P, bs, KV, d)).astype(dtype)
    ids = np.arange(1, P)                     # block 0 reserved sink
    tables = np.zeros((B, nb), np.int32)
    tables[:, :shared_prefix] = ids[:shared_prefix]
    nxt = shared_prefix
    for b in range(B):
        own = nb - shared_prefix
        tables[b, shared_prefix:] = ids[nxt:nxt + own]
        nxt += own
    return k_pool, v_pool, jnp.asarray(tables)


def _window_kv(key, B, W, KV, d, dtype=jnp.float32):
    kk, kv = jax.random.split(key)
    return (jax.random.normal(kk, (B, W, KV, d)).astype(dtype),
            jax.random.normal(kv, (B, W, KV, d)).astype(dtype))


@pytest.mark.parametrize("bs", [16, 64, 128])
@pytest.mark.parametrize("W", [1, 4, 16])
def test_fused_kernel_matches_ref_and_dense(bs, W):
    B, H, KV, d, nb = 2, 4, 2, 32, 3
    P = 1 + B * nb
    key = jax.random.PRNGKey(bs * 31 + W)
    kq, kp, kl, kn = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, W, H, d))
    k_pool, v_pool, tables = _pool_and_tables(kp, P, bs, nb, KV, d, B)
    k_new, v_new = _window_kv(kn, B, W, KV, d)
    # ragged: partially filled tail blocks, room left for the W window keys
    lengths = jax.random.randint(kl, (B,), 1, nb * bs - W)

    got, kp2, vp2 = paged_attention(q, k_pool, v_pool, k_new, v_new, tables,
                                    lengths, interpret=True)
    # the fused commit is bitwise the reference scatter (sink excluded)
    rk = write_window_paged(k_pool, k_new, tables, lengths)
    rv = write_window_paged(v_pool, v_new, tables, lengths)
    np.testing.assert_array_equal(np.asarray(kp2)[1:], np.asarray(rk)[1:])
    np.testing.assert_array_equal(np.asarray(vp2)[1:], np.asarray(rv)[1:])
    want = paged_attention_ref(q, rk, rv, tables, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # vs the dense op over the post-write gathered view (allclose: tiling)
    kd, vd = gather_view(rk, tables), gather_view(rv, tables)
    dense = decode_attention(q, kd, vd, lengths, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_fused_kernel_bitwise_vs_dense_kernel():
    """Same tile width -> identical online-softmax op sequence: the fused
    paged kernel must reproduce the dense flash-decode kernel (run over the
    post-write gathered view, ``block_k`` = the compute block's keys)
    bit-for-bit, over several blocks and a ragged last one."""
    B, W, H, KV, d, bs, nb = 2, 8, 4, 2, 32, 16, 80
    P = 1 + B * nb
    ppb = pages_per_block(nb=nb, bs=bs, KV=KV, widths=(d, d), R=H // KV * W,
                          dv=d, W=W, itemsize=4)
    assert 1 < ppb < nb and nb % ppb                  # ragged last block
    key = jax.random.PRNGKey(7)
    kq, kp, kl, kn = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, W, H, d))
    k_pool, v_pool, tables = _pool_and_tables(kp, P, bs, nb, KV, d, B)
    k_new, v_new = _window_kv(kn, B, W, KV, d)
    lengths = jax.random.randint(kl, (B,), 1, nb * bs - W)

    paged, kp2, vp2 = paged_attention(q, k_pool, v_pool, k_new, v_new,
                                      tables, lengths, interpret=True)
    G = H // KV
    kd = jnp.repeat(gather_view(kp2, tables), G, axis=2)
    vd = jnp.repeat(gather_view(vp2, tables), G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, W, d)
    kf = kd.transpose(0, 2, 1, 3).reshape(B * H, nb * bs, d)
    vf = vd.transpose(0, 2, 1, 3).reshape(B * H, nb * bs, d)
    dense = decode_attention_kernel(qf, kf, vf, jnp.repeat(lengths, H),
                                    block_k=ppb * bs, interpret=True)
    dense = dense.reshape(B, H, W, d).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))


def _grouped_kernel(q, k_pool, v_pool, k_new, v_new, tables, lengths, *,
                    ppb, window=0):
    """``paged_attention``'s kernel path at a chosen ``ppb``."""
    B, W, H, d = q.shape
    KV = k_pool.shape[2]
    G = H // KV
    qg = (q.reshape(B, W, KV, G, d).transpose(0, 2, 3, 1, 4)
          .reshape(B, KV, G * W, d))
    out, kp, vp = paged_decode_kernel(qg, k_pool, v_pool, k_new, v_new,
                                      tables, lengths, W=W, ppb=ppb,
                                      window=window, interpret=True)
    out = (out.reshape(B, KV, G, W, d).transpose(0, 3, 1, 2, 4)
           .reshape(B, W, H, d))
    return out, kp, vp


# bs 16; T = ppb * 16 keys per compute block. ``used`` keeps only the pages
# a row uses in its table (the rest point at the sink, as in the engine).
MULTI_PAGE_CASES = {
    "ends-inside-block": dict(W=4, nb=12, ppb=4, lengths=[37, 100]),
    "ends-at-block-start": dict(W=4, nb=12, ppb=4, lengths=[61, 125]),
    "ends-at-block-end": dict(W=4, nb=12, ppb=4, lengths=[60, 124]),
    "span-straddles-blocks": dict(W=8, nb=12, ppb=4, lengths=[60, 123]),
    "prefill-w64-five-pages": dict(W=64, nb=12, ppb=4, lengths=[47]),
    "rows-far-below-nb": dict(W=4, nb=40, ppb=4, lengths=[5, 30],
                              used=True),
    "shared-prefix": dict(W=4, nb=8, ppb=2, lengths=[40, 71], shared=2),
    "sliding-window-skips-blocks": dict(W=4, nb=12, ppb=2, lengths=[150, 70],
                                        window=24),
}


@pytest.mark.parametrize("case", sorted(MULTI_PAGE_CASES))
def test_multi_page_blocks_match_ref(case):
    c = MULTI_PAGE_CASES[case]
    W, nb, ppb, window = c["W"], c["nb"], c["ppb"], c.get("window", 0)
    shared = c.get("shared", 0)
    lengths = jnp.asarray(c["lengths"], jnp.int32)
    B, H, KV, d, bs = len(c["lengths"]), 4, 2, 32, 16
    P = 1 + shared + B * (nb - shared)
    key = jax.random.PRNGKey(sum(map(ord, case)))
    kq, kp, kn = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, W, H, d))
    k_pool, v_pool, tables = _pool_and_tables(kp, P, bs, nb, KV, d, B,
                                              shared_prefix=shared)
    if c.get("used"):
        used = (lengths[:, None] + W - 1) // bs >= jnp.arange(nb)[None]
        tables = jnp.where(used, tables, 0)
        k_pool = k_pool.at[0].set(1e9)                # the sink never counts
        v_pool = v_pool.at[0].set(-1e9)
    k_new, v_new = _window_kv(kn, B, W, KV, d)

    got, kp2, vp2 = _grouped_kernel(q, k_pool, v_pool, k_new, v_new, tables,
                                    lengths, ppb=ppb, window=window)
    rk = write_window_paged(k_pool, k_new, tables, lengths)
    rv = write_window_paged(v_pool, v_new, tables, lengths)
    np.testing.assert_array_equal(np.asarray(kp2)[1:], np.asarray(rk)[1:])
    np.testing.assert_array_equal(np.asarray(vp2)[1:], np.asarray(rv)[1:])
    want = paged_attention_ref(q, rk, rv, tables, lengths, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if shared:                                   # prefix pages only read
        ids = np.asarray(tables[0, :shared])
        np.testing.assert_array_equal(np.asarray(kp2)[ids],
                                      np.asarray(k_pool)[ids])
        np.testing.assert_array_equal(np.asarray(vp2)[ids],
                                      np.asarray(v_pool)[ids])


@pytest.mark.parametrize("window", [0, 24])
def test_fused_kernel_sliding_window(window):
    B, W, H, KV, d, bs, nb = 2, 4, 4, 1, 32, 16, 4
    P = 1 + B * nb
    key = jax.random.PRNGKey(window + 1)
    kq, kp, kn = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, W, H, d))
    k_pool, v_pool, tables = _pool_and_tables(kp, P, bs, nb, KV, d, B)
    k_new, v_new = _window_kv(kn, B, W, KV, d)
    lengths = jnp.asarray([37, 11])
    got, kp2, vp2 = paged_attention(q, k_pool, v_pool, k_new, v_new, tables,
                                    lengths, window=window, interpret=True)
    rk = write_window_paged(k_pool, k_new, tables, lengths)
    rv = write_window_paged(v_pool, v_new, tables, lengths)
    want = paged_attention_ref(q, rk, rv, tables, lengths, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(kp2)[1:], np.asarray(rk)[1:])


def test_shared_prefix_blocks_read_identically_and_stay_unwritten():
    """Two sequences whose tables alias the same physical prefix blocks and
    have equal lengths must produce identical outputs for identical queries
    — the prefix-cache sharing contract at the kernel level — and the fused
    epilogue must never write a shared prefix block (they sit strictly
    below the window span)."""
    B, W, H, KV, d, bs, nb = 2, 4, 2, 2, 16, 8, 3
    P = 1 + 2 + B * 1                         # 2 shared + 1 private each
    key = jax.random.PRNGKey(3)
    kq, kp, kn = jax.random.split(key, 3)
    q1 = jax.random.normal(kq, (1, W, H, d))
    q = jnp.concatenate([q1, q1], axis=0)
    k_pool, v_pool, tables = _pool_and_tables(kp, P, bs, nb, KV, d, B,
                                              shared_prefix=2)
    kn1, vn1 = _window_kv(kn, 1, W, KV, d)
    k_new = jnp.concatenate([kn1, kn1], axis=0)
    v_new = jnp.concatenate([vn1, vn1], axis=0)
    assert (np.asarray(tables[0, :2]) == np.asarray(tables[1, :2])).all()
    assert tables[0, 2] != tables[1, 2]
    # q_pos tops out at lengths + W - 1 = 15: every attended key lives in
    # the shared prefix blocks... except the window itself (merged)
    lengths = jnp.asarray([2 * bs - W, 2 * bs - W])
    out, kp2, vp2 = paged_attention(q, k_pool, v_pool, k_new, v_new, tables,
                                    lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))
    # shared prefix blocks strictly below the window stayed untouched
    shared = np.asarray(tables[0, :1])        # block 0 covers pos < 8 < 12
    np.testing.assert_array_equal(np.asarray(kp2)[shared],
                                  np.asarray(k_pool)[shared])
    np.testing.assert_array_equal(np.asarray(vp2)[shared],
                                  np.asarray(v_pool)[shared])


def test_sink_tail_blocks_never_contribute():
    """Table entries past the allocation point alias sink block 0: poisoning
    the sink must not change the output (causal masking kills the tail)."""
    B, W, H, KV, d, bs, nb = 1, 4, 2, 1, 16, 8, 4
    P = 1 + nb
    key = jax.random.PRNGKey(11)
    kq, kp, kn = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, W, H, d))
    k_pool, v_pool, _ = _pool_and_tables(kp, P, bs, nb, KV, d, B)
    k_new, v_new = _window_kv(kn, B, W, KV, d)
    tables = jnp.asarray([[1, 2, 0, 0]], jnp.int32)   # 2 real blocks + sink
    lengths = jnp.asarray([2 * bs - W], jnp.int32)
    base, _, _ = paged_attention(q, k_pool, v_pool, k_new, v_new, tables,
                                 lengths, interpret=True)
    poisoned_k = k_pool.at[0].set(1e9)
    poisoned_v = v_pool.at[0].set(-1e9)
    got, _, _ = paged_attention(q, poisoned_k, poisoned_v, k_new, v_new,
                                tables, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


# (W, nb, ppb, lengths): ppb None is what paged_latent_attention picks (one
# block over the whole table here); the explicit ppb cases put row 1's
# window across the boundary of two compute blocks
@pytest.mark.parametrize("W,nb,ppb,lengths", [
    (1, 3, None, None), (4, 3, None, None),
    (4, 6, 1, [20, 30]), (4, 6, 2, [20, 62])],
    ids=["1", "4", "4-ppb1-straddle", "4-ppb2-straddle"])
def test_fused_latent_kernel_matches_ref(W, nb, ppb, lengths):
    B, H, r, dr, bs = 2, 4, 24, 16, 16
    P = 1 + B * nb
    key = jax.random.PRNGKey(W if ppb is None else 40 + ppb)
    k1, k2, k3, k4, kl, kn = jax.random.split(key, 6)
    q_lat = jax.random.normal(k1, (B, W, H, r))
    q_rope = jax.random.normal(k2, (B, W, H, dr))
    c_pool = jax.random.normal(k3, (P, bs, r))
    kr_pool = jax.random.normal(k4, (P, bs, dr))
    c_new = jax.random.normal(kn, (B, W, r))
    kr_new = jax.random.normal(jax.random.fold_in(kn, 1), (B, W, dr))
    ids = np.arange(1, P).reshape(B, nb)
    tables = jnp.asarray(ids, jnp.int32)
    lengths = (jax.random.randint(kl, (B,), 1, nb * bs - W) if lengths is None
               else jnp.asarray(lengths, jnp.int32))
    scale = 1.0 / np.sqrt(r + dr)
    if ppb is None:
        got, c2, kr2 = paged_latent_attention(q_lat, q_rope, c_pool, kr_pool,
                                              c_new, kr_new, tables, lengths,
                                              scale, interpret=True)
    else:
        out, c2, kr2 = paged_latent_kernel(
            q_lat.transpose(0, 2, 1, 3).reshape(B, 1, H * W, r),
            q_rope.transpose(0, 2, 1, 3).reshape(B, 1, H * W, dr),
            c_pool[:, :, None], kr_pool[:, :, None], c_new[:, :, None],
            kr_new[:, :, None], tables, lengths, W=W, ppb=ppb, scale=scale,
            interpret=True)
        got = out.reshape(B, H, W, r).transpose(0, 2, 1, 3)
        c2, kr2 = c2[:, :, 0], kr2[:, :, 0]
    rc = write_window_paged(c_pool, c_new, tables, lengths)
    rkr = write_window_paged(kr_pool, kr_new, tables, lengths)
    want = paged_latent_ref(q_lat, q_rope, rc, rkr, tables, lengths,
                            scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # both latent pools committed bitwise (sink excluded)
    np.testing.assert_array_equal(np.asarray(c2)[1:], np.asarray(rc)[1:])
    np.testing.assert_array_equal(np.asarray(kr2)[1:], np.asarray(rkr)[1:])


def test_paged_window_write_bitwise_and_inactive_routing():
    """The standalone aliased writeback is bitwise the reference scatter:
    window rows land at table-resolved physical offsets; rows whose table
    is all-zero (cleared slots) land in the sink block; inactive rows never
    touch their real blocks."""
    P, bs, KV, d = 7, 4, 1, 8
    B, W, nb = 3, 3, 3
    key = jax.random.PRNGKey(17)
    pool = jax.random.normal(key, (P, bs, KV, d))
    new = jax.random.normal(jax.random.fold_in(key, 1), (B, W, KV, d))
    tables = jnp.asarray([[2, 3, 4], [5, 6, 0], [0, 0, 0]], jnp.int32)
    cache_len = jnp.asarray([3, 0, 0], jnp.int32)   # row 0 straddles blocks
    got = paged_window_write(pool, new, tables, cache_len, interpret=True)
    want = write_window_paged(pool, new, tables, cache_len)
    np.testing.assert_array_equal(np.asarray(got)[1:], np.asarray(want)[1:])

    active = jnp.asarray([1, 0, 1], jnp.int32)
    got_a = paged_window_write(pool, new, tables, cache_len, active=active,
                               interpret=True)
    want_a = write_window_paged(pool, new, tables, cache_len,
                                active=jnp.asarray([True, False, True]))
    np.testing.assert_array_equal(np.asarray(got_a)[1:],
                                  np.asarray(want_a)[1:])
    # the inactive row's real blocks kept their old contents
    np.testing.assert_array_equal(np.asarray(got_a)[5:7],
                                  np.asarray(pool)[5:7])


def test_write_window_paged_targets_physical_slots():
    """Reference semantics anchor: window rows land at table-resolved
    physical offsets; rows whose table is all-zero (cleared slots) land in
    the sink block."""
    P, bs, KV, d = 5, 4, 1, 8
    B, W, nb = 2, 3, 3
    pool = jnp.zeros((P, bs, KV, d))
    new = jnp.ones((B, W, KV, d)) * jnp.arange(1, B * W + 1).reshape(
        B, W, 1, 1)
    tables = jnp.asarray([[2, 3, 4], [0, 0, 0]], jnp.int32)
    cache_len = jnp.asarray([3, 0], jnp.int32)   # row 0 straddles blocks
    out = np.asarray(write_window_paged(pool, new, tables, cache_len))
    # row 0: positions 3,4,5 -> block 2 slot 3, block 3 slots 0,1
    assert out[2, 3, 0, 0] == 1 and out[3, 0, 0, 0] == 2
    assert out[3, 1, 0, 0] == 3
    # row 1 (cleared): positions 0..2 -> sink block 0
    assert (out[0, :3, 0, 0] == [4, 5, 6]).all()
    # untouched slots stay zero
    assert out[4].sum() == 0 and out[2, :3].sum() == 0


def test_dense_decode_kernel_ragged_tail_no_pad():
    """Satellite: S not divisible by block_k must be masked in-kernel (the
    old path jnp.pad'ed a full cache copy); oracle equality at a ragged S."""
    B, W, H, KV, d, S = 2, 4, 2, 1, 32, 150
    key = jax.random.PRNGKey(5)
    kq, kk, kv, kl = jax.random.split(key, 4)
    q = jax.random.normal(kq, (B, W, H, d))
    k = jax.random.normal(kk, (B, S, KV, d))
    v = jax.random.normal(kv, (B, S, KV, d))
    lengths = jax.random.randint(kl, (B,), 1, S - W)
    got = decode_attention(q, k, v, lengths, block_k=64, interpret=True)
    want = decode_attention(q, k, v, lengths, use_kernel=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
