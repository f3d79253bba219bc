"""Units for the contract engine's measurement layer (DESIGN.md §17):
jaxpr primitive census with sub-jaxpr recursion + rank filtering, dtype
byte parsing, and the async-collective HLO regression."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (EqnSite, count_jaxpr_primitives, find_collectives,
                            find_dtype_leaks, find_jaxpr_primitives,
                            parse_collective_bytes, parse_shape_bytes)


def _jaxpr(fn, *args):
    return jax.jit(fn).trace(*args).jaxpr


# ---------------------------------------------------------------------------
# sub-jaxpr recursion
# ---------------------------------------------------------------------------

def test_counts_recurse_into_while_loop():
    def fn(pool):
        def body(c):
            i, p = c
            return i + 1, p.at[i].set(p[i] + 1.0)
        return jax.lax.while_loop(lambda c: c[0] < 3, body,
                                  (jnp.int32(0), pool))
    counts = count_jaxpr_primitives(_jaxpr(fn, jnp.zeros((4, 2, 8))),
                                    ("scatter",), min_rank=3)
    assert counts["scatter"] == 1


def test_counts_recurse_into_scan():
    def fn(pool, idx):
        def step(p, i):
            return p.at[i].set(0.0), i
        out, _ = jax.lax.scan(step, pool, idx)
        return out
    counts = count_jaxpr_primitives(
        _jaxpr(fn, jnp.zeros((4, 2, 8)), jnp.arange(3)),
        ("scatter",), min_rank=3)
    assert counts["scatter"] == 1


def test_counts_recurse_into_pjit():
    inner = jax.jit(lambda p, i: p.at[i].set(1.0))

    def fn(pool, i):
        return inner(pool, i)
    sites = find_jaxpr_primitives(
        _jaxpr(fn, jnp.zeros((4, 2, 8)), jnp.int32(1)),
        ("scatter",), min_rank=3)
    assert len(sites) == 1
    assert "jit" in sites[0].path        # evidence names the nesting


def test_counts_recurse_into_pallas_body():
    pl = pytest.importorskip("jax.experimental.pallas")

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def fn(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)
    jx = _jaxpr(fn, jnp.ones((8, 8)))
    assert count_jaxpr_primitives(jx, ("pallas_call",))["pallas_call"] == 1
    # the kernel body's mul is found THROUGH the pallas_call sub-jaxpr
    sites = find_jaxpr_primitives(jx, ("mul",))
    assert any("pallas_call" in s.path for s in sites)


# ---------------------------------------------------------------------------
# rank filtering + evidence records
# ---------------------------------------------------------------------------

def test_rank_filter_separates_pool_from_bookkeeping():
    def fn(pool, row, i):
        return pool.at[i].set(1.0), row.at[i].set(2)
    jx = _jaxpr(fn, jnp.zeros((4, 2, 8)), jnp.zeros((4,), jnp.int32),
                jnp.int32(1))
    assert count_jaxpr_primitives(jx, ("scatter",))["scatter"] == 2
    assert count_jaxpr_primitives(jx, ("scatter",), min_rank=3)[
        "scatter"] == 1
    sites = find_jaxpr_primitives(jx, ("scatter",), min_rank=3)
    assert [s.rank for s in sites] == [3]
    assert isinstance(sites[0], EqnSite) and "scatter" in str(sites[0])


def test_find_dtype_leaks_under_x64():
    def fn(x):
        return x.astype("float64") * 2.0
    with jax.enable_x64(True):
        jx = jax.jit(fn).trace(jnp.ones((3,), jnp.float32)).jaxpr
    leaks = find_dtype_leaks(jx)
    assert leaks and all("float64" not in s.primitive for s in leaks)
    assert find_dtype_leaks(_jaxpr(lambda x: x * 2, jnp.ones(3))) == []


# ---------------------------------------------------------------------------
# dtype-byte parsing
# ---------------------------------------------------------------------------

def test_parse_shape_bytes_dtypes():
    assert parse_shape_bytes("f32[16,128]") == 16 * 128 * 4
    assert parse_shape_bytes("bf16[4,8]") == 4 * 8 * 2
    assert parse_shape_bytes("(s32[10], u8[3])") == 40 + 3
    assert parse_shape_bytes("pred[7]") == 7
    assert parse_shape_bytes("f64[2]") == 16
    assert parse_shape_bytes("opaque[]") == 0


# ---------------------------------------------------------------------------
# async collective regression (the PR 10 parser fix)
# ---------------------------------------------------------------------------

ASYNC_HLO = """
ENTRY main {
  p0 = f32[16,128]{1,0} parameter(0)
  p1 = bf16[4,8]{1,0} parameter(1)
  ars = f32[16,128]{1,0} all-reduce-start(p0), to_apply=add
  ard = f32[16,128]{1,0} all-reduce-done(ars)
  ags = (bf16[4,8]{1,0}, bf16[8,8]{1,0}) all-gather-start(p1), dimensions={0}
  agd = bf16[8,8]{1,0} all-gather-done(ags)
  cps = f32[16,128]{1,0} collective-permute-start(ard), source_target_pairs={{0,1}}
  cpd = f32[16,128]{1,0} collective-permute-done(cps)
  ROOT out = f32[16,128]{1,0} add(ard, cpd)
}
"""


def test_async_collectives_fold_into_sync_counts():
    out = parse_collective_bytes(ASYNC_HLO)
    assert out["all-reduce"]["count"] == 1
    assert out["all-reduce"]["bytes"] == 16 * 128 * 4
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == (4 * 8 + 8 * 8) * 2
    assert out["collective-permute"]["count"] == 1
    # -done ops consume the handle, not new bytes: never double-counted
    assert sum(v["count"] for v in out.values()) == 3


def test_find_collectives_names_the_hlo_line():
    recs = find_collectives(ASYNC_HLO)
    ops = {r["op"] for r in recs}
    assert ops == {"all-reduce-start", "all-gather-start",
                   "collective-permute-start"}
    ar = next(r for r in recs if r["op"] == "all-reduce-start")
    assert ar["line_no"] == 5 and "all-reduce-start" in ar["line"]


def test_sync_collectives_still_parse():
    hlo = """
  %ar = f32[16,128]{1,0} all-reduce(%x), replica_groups={}
  %rs = f32[8]{0} reduce-scatter(%y), dimensions={0}
"""
    out = parse_collective_bytes(hlo)
    assert out["all-reduce"]["count"] == 1
    assert out["reduce-scatter"] == {"bytes": 32, "count": 1}
