"""Topology/router units — host-side: slot and block-pool partition math,
pool-pressure admission routing, per-shard stats merging, the
priority/EDF/FIFO queue order, and the engine's kernel dispatch on a
(1x1) mesh."""
import numpy as np
import pytest

from repro.serving import Request, ServingTopology, ShardedBlockPool
from repro.serving.admission import AdmissionQueue


class FakeMesh:
    """Only .shape and .axis_names are consulted by the partition math."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_single_device_topology_is_one_shard():
    t = ServingTopology()
    assert t.data_size == 1
    assert t.auto_axes == frozenset()
    assert t.slots_per_shard(4) == 4
    assert t.shard_of_slot(3, 4) == 0
    assert list(t.slot_range(0, 4)) == [0, 1, 2, 3]
    assert t.block_offset(0, 17) == 0

    def fn(*a):
        return a

    # without a mesh the round wrapper is the identity (plain jit path)
    assert t.wrap_round(fn, None, 6, 4) is fn


def test_partition_math_over_data_shards():
    t = ServingTopology(FakeMesh({"data": 2, "model": 4}))
    assert t.data_size == 2
    assert t.auto_axes == frozenset({"model"})
    assert t.slots_per_shard(8) == 4
    assert [t.shard_of_slot(b, 8) for b in range(8)] == [0] * 4 + [1] * 4
    assert list(t.slot_range(1, 8)) == [4, 5, 6, 7]
    # global pool id of shard 1's sink = its sub-pool base
    assert t.block_offset(1, 33) == 33
    with pytest.raises(AssertionError):
        t.slots_per_shard(5)            # batch must divide over shards


def test_route_picks_max_headroom_with_ties_to_lowest():
    assert ShardedBlockPool.route(3, {0: 5, 1: 9}) == 1
    assert ShardedBlockPool.route(3, {1: 9, 0: 5}) == 1
    assert ShardedBlockPool.route(3, {0: 9, 1: 9}) == 0   # tie -> lowest id
    assert ShardedBlockPool.route(6, {0: 5, 1: 4}) is None  # nobody fits
    assert ShardedBlockPool.route(5, {0: 5, 1: 4}) == 0   # exact fit admits
    assert ShardedBlockPool.route(1, {}) is None          # no free slots


def test_sub_pools_are_independent():
    pool = ShardedBlockPool(2, 8, 4)
    got = pool.manager(0).alloc(3)
    assert pool.available(0) == 4 and pool.available(1) == 7
    assert pool.available() == 11
    assert pool.blocks_in_use() == 3
    # shard-local ids: both shards can hand out the same local id
    other = pool.manager(1).alloc(3)
    assert got == other
    pool.manager(0).release_all(got)
    pool.manager(1).release_all(other)
    assert pool.available() == 14 and pool.blocks_in_use() == 0


def test_prefix_caches_do_not_cross_shards_and_stats_merge():
    pool = ShardedBlockPool(2, 8, 2)
    prompt = np.asarray([5, 6, 7, 8, 9])
    m0 = pool.manager(0)
    blocks = m0.alloc(2)
    from repro.serving import chain_hashes
    keys = chain_hashes(prompt, 2)
    for b, k in zip(blocks, keys):
        m0.register(b, k)
    # same prompt hits on shard 0, misses on shard 1 (per-shard cache)
    hits0, _ = m0.lookup_prefix(prompt, 2)
    assert hits0 == blocks
    hits1, _ = pool.manager(1).lookup_prefix(prompt, 2)
    assert hits1 == []
    merged = pool.stats_export()
    assert merged["prefix_hits"] == 2
    assert merged["prefix_misses"] == 2
    assert merged["prefix_hit_rate"] == 0.5


def test_queue_orders_priority_then_deadline_then_fifo():
    q = AdmissionQueue()
    reqs = [Request(uid=0, prompt=np.ones(1), new_tokens=1),
            Request(uid=1, prompt=np.ones(1), new_tokens=1, deadline=500.0),
            Request(uid=2, prompt=np.ones(1), new_tokens=1, deadline=5000.0),
            Request(uid=3, prompt=np.ones(1), new_tokens=1, priority=-1),
            Request(uid=4, prompt=np.ones(1), new_tokens=1)]
    for r in reqs:
        q.push(r)
    # priority class first; then earliest deadline; deadline-free requests
    # sort last and stay FIFO among themselves
    assert [q.pop().uid for _ in range(len(reqs))] == [3, 1, 2, 0, 4]


def test_deadline_time_and_miss_flag():
    r = Request(uid=0, prompt=np.ones(1), new_tokens=1)
    assert r.deadline_time == float("inf")
    r.finish_time = 1e12
    assert not r.missed_deadline
    d = Request(uid=1, prompt=np.ones(1), new_tokens=1, deadline=2.0)
    d.submit_time = 100.0
    assert d.deadline_time == 102.0
    d.finish_time = 101.5
    assert not d.missed_deadline
    d.finish_time = 102.5
    assert d.missed_deadline


def test_mesh_engine_interprets_pallas_calls():
    """GSPMD cannot partition a compiled Mosaic kernel, so an engine on a
    mesh (even 1x1) interprets every Pallas call and attends through the
    gather view; a single-device engine leaves the choice to the backend."""
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models.transformer import TransformerLM
    from repro.serving import ServingEngine

    cfg = get_config("qwen3-1.7b", reduced=True)
    params = TransformerLM.init(jax.random.PRNGKey(0), cfg)
    kw = dict(batch=1, max_len=32, block_size=4)
    solo = ServingEngine(cfg, params, **kw)
    assert solo.kernel_interpret is None
    assert solo._view(None, None).interpret is None
    meshed = ServingEngine(cfg, params, **kw,
                           topology=ServingTopology(make_host_mesh(1, 1)))
    assert meshed.kernel_interpret is True
    assert meshed.use_attention_kernel is False
    assert meshed._view(None, None) == (None, None, False, True)
