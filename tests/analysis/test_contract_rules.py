"""One deliberately-violating fixture per contract rule (DESIGN.md §17):
the engine must be shown to CATCH, not just pass. Each fixture asserts
the contract fails with a structured report naming the offending eqn /
HLO line."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (Contract, ContractViolationError,
                            DonationAliasCovers, MaxLiveBytes, NoCollectives,
                            NoF64Leaks, NoHostCallbacks, NoPoolRankedScatters,
                            Program, RecompileHazard, check_program, require)

_ROOT = os.path.join(os.path.dirname(__file__), "..", "..")


def test_host_callback_fixture_fails():
    def fn(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    rep = check_program(fn, (jnp.ones((3,)),),
                        Contract("T", [NoHostCallbacks()]))
    assert not rep.ok
    v = rep.violations[0]
    assert v.rule == "NoHostCallbacks"
    assert "pure_callback" in v.evidence["eqn"]
    assert rep.metrics["host_callbacks"] == 1


def test_pool_ranked_scatter_fixture_fails_with_rank_evidence():
    def fn(pool, i, val):
        return pool.at[i].set(val)
    rep = check_program(
        fn, (jnp.zeros((4, 2, 8)), jnp.asarray([1]), jnp.ones((1, 2, 8))),
        Contract("T", [NoPoolRankedScatters(min_rank=3)]))
    assert not rep.ok
    v = rep.violations[0]
    assert v.rule == "NoPoolRankedScatters" and v.evidence["rank"] == 3
    assert "scatter" in v.evidence["eqn"]
    # the same program passes a rank-4 threshold: rule is parameterized
    assert check_program(
        fn, (jnp.zeros((4, 2, 8)), jnp.asarray([1]), jnp.ones((1, 2, 8))),
        Contract("T", [NoPoolRankedScatters(min_rank=4)])).ok


def test_unaliased_donation_fixture_fails():
    def fn(pool, x):
        return pool + x, x * 2
    args = (jnp.zeros((64, 64)), jnp.ones((1,)))
    # donated: aliasing established, rule passes
    donated = jax.jit(fn, donate_argnums=(0,))
    assert check_program(donated, args,
                         Contract("T", [DonationAliasCovers((0,))])).ok
    # NOT donated: zero aliasing, the contract must fail with byte evidence
    rep = check_program(jax.jit(fn), args,
                        Contract("T", [DonationAliasCovers((0,))]))
    assert not rep.ok
    v = rep.violations[0]
    assert v.rule == "DonationAliasCovers"
    assert v.evidence["alias_bytes"] == 0
    assert v.evidence["pool_bytes"] == 64 * 64 * 4


def test_f64_leak_fixture_fails():
    def fn(x):
        return x.astype("float64") * 2.0
    with jax.enable_x64(True):
        rep = check_program(fn, (jnp.ones((3,), jnp.float32),),
                            Contract("T", [NoF64Leaks()]))
    assert not rep.ok
    assert all(v.rule == "NoF64Leaks" for v in rep.violations)
    assert any("f64" in v.evidence["eqn"] for v in rep.violations)


def test_max_live_bytes_budget():
    def fn(x):
        return x @ x
    args = (jnp.ones((64, 64)),)
    assert check_program(fn, args,
                         Contract("T", [MaxLiveBytes(1 << 30)])).ok
    rep = check_program(fn, args, Contract("T", [MaxLiveBytes(100)]))
    assert not rep.ok
    v = rep.violations[0]
    assert v.rule == "MaxLiveBytes" and v.evidence["live_bytes"] > 100


def test_recompile_hazard_trips_on_shape_churn():
    rule = RecompileHazard(max_shapes=3)
    label = "test-recompile-hazard-fixture"
    contract = Contract("T", [rule])

    def fn(x):
        return x * 2
    reports = [check_program(fn, (jnp.ones((n,)),), contract, label=label)
               for n in range(1, 6)]
    assert all(r.ok for r in reports[:3])      # within budget
    assert not reports[-1].ok                  # 5th distinct shape trips
    v = reports[-1].violations[0]
    assert v.rule == "RecompileHazard"
    assert v.evidence["distinct_shapes"] == 5


def test_require_raises_structured_error():
    def fn(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    rep = check_program(fn, (jnp.ones((3,)),),
                        Contract("T", [NoHostCallbacks()]))
    with pytest.raises(ContractViolationError) as ei:
        require(rep)
    assert "NoHostCallbacks" in str(ei.value)
    assert ei.value.report is rep
    # and it is an AssertionError subclass for legacy harnesses
    assert isinstance(ei.value, AssertionError)


COLLECTIVE_SCRIPT = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.analysis import Contract, NoCollectives, check_program

    mesh = Mesh(np.array(jax.devices()).reshape(2,), ("data",))

    def fn(x):
        return jax.shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P())(x)

    rep = check_program(fn, (jnp.arange(8, dtype=jnp.float32),),
                        Contract("SEEDED", [NoCollectives()]),
                        label="seeded-collective")
    print(json.dumps({
        "ok": rep.ok,
        "rules": [v.rule for v in rep.violations],
        "sites": [v.site for v in rep.violations],
        "bytes": [v.evidence["bytes"] for v in rep.violations]}))
""")


def test_seeded_collective_fixture_fails_with_hlo_line():
    """A psum under shard_map on 2 forced devices MUST trip NoCollectives,
    and the violation names the HLO line (subprocess: the main test
    process keeps its single-device view)."""
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", COLLECTIVE_SCRIPT], env=env,
                         capture_output=True, text=True, cwd=_ROOT)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-4000:])
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert not rec["ok"]
    assert "NoCollectives" in rec["rules"]
    assert any("HLO line" in s and "all-reduce" in s for s in rec["sites"])
    assert all(b > 0 for b in rec["bytes"])


def test_program_hlo_only_fixture_rejects_jaxpr_rules():
    prog = Program(hlo_text="ENTRY main {}", label="hlo-only")
    with pytest.raises(ValueError):
        _ = prog.jaxpr


def test_scatter_pool_shape_targeting_spares_non_pool_writes():
    """MoE dispatch buffers and recurrent state rows are high-rank
    scatters the round runs by design; targeting the rule at the exact
    pool leaf shapes must spare them while the SAME program's real
    pool-shaped scatter still fails."""
    def fn(pool, state, i, pv, sv):
        return pool.at[i].set(pv), state.at[i].set(sv)
    args = (jnp.zeros((4, 2, 8)), jnp.zeros((4, 1, 16)),
            jnp.asarray([1]), jnp.ones((1, 2, 8)), jnp.ones((1, 1, 16)))
    rep = check_program(fn, args, Contract("T", [NoPoolRankedScatters()]))
    assert len(rep.violations) == 2      # rank proxy: both rank-3 writes
    rep = check_program(fn, args, Contract("T", [
        NoPoolRankedScatters(pool_shapes={(4, 2, 8)})]))
    assert len(rep.violations) == 1      # state write spared, pool caught
    assert rep.violations[0].evidence["shape"] == [4, 2, 8]
    # empty pool-shape set (pure-recurrent arch: no KV pool) passes all
    assert check_program(fn, args, Contract("T", [
        NoPoolRankedScatters(pool_shapes=frozenset())])).ok
