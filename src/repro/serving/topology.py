"""Serving topology: the mesh-placement layer of the serving runtime
(DESIGN.md §10).

``ServingTopology`` describes how one ``ServingEngine`` maps onto a device
mesh and owns every placement decision the engine makes:

* **Slot partition** — the ``batch`` slots are split into ``data_size``
  contiguous ranges; shard ``s`` owns slots ``[s*B_local, (s+1)*B_local)``.
* **Block sub-pools** — the physical block pool is per-data-shard: shard
  ``s`` owns global blocks ``[s*P_local, (s+1)*P_local)`` and its block
  tables store *shard-local* ids. Each sub-pool has its own reserved sink
  block (local id 0), so masked scatter lanes never cross shards.
* **Round wrapping** — the verify round / jitted step runs under
  ``shard_map`` manual over the ``data`` axis: every shard decodes its own
  rows against its own sub-pool with its own local tables. Block-table
  indirection is shard-local *by construction* — no gather ever sees a
  remote block id, so the round hot path lowers with zero cross-shard
  collectives (asserted via HLO inspection in
  tests/serving/test_mesh_engine.py). The device-resident round *loop*
  (DESIGN.md §11) preserves this: the whole ``lax.while_loop`` sits inside
  the per-shard body and each shard's stop condition reads only its OWN
  rows, so shards may run different trip counts and the stop test needs no
  cross-shard reduction — extra rounds on an early-finishing shard are
  token-exact no-ops. Other mesh axes (``model``, ``pod``) stay *auto*:
  GSPMD places tensor-sharded params there (only standard TP reductions
  can appear, never table-indexed traffic).
* **Exactness** — per-request noise streams (``Request.seq_id``) are
  placement-independent and the round body is row-local, so a mesh engine
  emits tokens bit-identical to the single-device engine and to solo
  ``PredictiveSampler.generate``.

``ServingTopology()`` (no mesh) is the single-device degenerate case: one
shard, plain ``jax.jit``, no placement — the engine always goes through
this layer, so there is exactly one code path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P



class ServingTopology:
    """Mesh + axis naming + partition math for a mesh-sharded engine.

    ``mesh=None`` = single device (one shard). ``data_axis`` rows/pools are
    manually sharded; every other mesh axis is left to GSPMD (``auto``) so
    ``param_shardings``-style tensor parallelism over ``model`` composes.
    """

    def __init__(self, mesh=None, data_axis: str = "data"):
        if mesh is not None:
            assert data_axis in mesh.axis_names, (data_axis, mesh.axis_names)
        self.mesh = mesh
        self.data_axis = data_axis

    @property
    def data_size(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.shape[self.data_axis])

    @property
    def auto_axes(self) -> frozenset:
        """Mesh axes of size > 1 besides ``data_axis``: the axes that carry
        tensor-parallel params. The round's ``shard_map`` is manual over
        ``data_axis`` alone, so GSPMD partitions these (and any size-1 axis)
        automatically; an empty set means the engine is purely
        data-parallel."""
        if self.mesh is None:
            return frozenset()
        return frozenset(a for a in self.mesh.axis_names
                         if a != self.data_axis and self.mesh.shape[a] > 1)

    def __repr__(self):
        if self.mesh is None:
            return "ServingTopology(single-device)"
        return (f"ServingTopology(mesh={dict(self.mesh.shape)}, "
                f"data_axis={self.data_axis!r})")

    # -- slot / block partition math (host-side bookkeeping) ---------------
    def slots_per_shard(self, batch: int) -> int:
        assert batch % self.data_size == 0, \
            f"batch {batch} not divisible by data shards {self.data_size}"
        return batch // self.data_size

    def shard_of_slot(self, b: int, batch: int) -> int:
        return b // self.slots_per_shard(batch)

    def slot_range(self, shard: int, batch: int) -> range:
        per = self.slots_per_shard(batch)
        return range(shard * per, (shard + 1) * per)

    def global_slot(self, shard: int, local_row: int, batch: int) -> int:
        """Inverse of the shard-local row numbering the round program sees:
        the global batch slot of ``local_row`` on ``shard``. The in-loop
        adoption scan (DESIGN.md §15) reports displaced episodes by local
        row; the harvest walk maps them back through here. Same contract
        for the shard-major staged-descriptor arrays: descriptor ``i`` of
        ``shard`` lives at flat index ``shard * S + i``, matching how
        ``put_batch`` splits a leading dimension across the data axis."""
        per = self.slots_per_shard(batch)
        assert 0 <= local_row < per, (local_row, per)
        return shard * per + local_row

    def block_offset(self, shard: int, blocks_per_shard: int) -> int:
        """Global pool id of a shard's local block 0 (its reserved sink)."""
        return shard * blocks_per_shard

    # -- host cache tier (DESIGN.md §13) ------------------------------------
    def host_tier(self, capacity_bytes: int, staging_depth: int = 2, *,
                  integrity: bool = True, faults=None, breaker=None,
                  disk=None):
        """Build the engine's host cache tier for this topology: one arena
        (a single shared byte budget for the whole process — a hot shard may
        use headroom an idle one is not) partitioned into per-data-shard key
        namespaces, mirroring the per-shard device prefix caches (block
        contents never cross shards, so neither do their host copies).
        ``integrity``/``faults``/``breaker`` configure the §14 fault layer
        (checksum verification, injection seams, circuit breaker); ``disk``
        is an optional §16 :class:`DiskTier` below the arena (one directory
        for the process — keys carry the shard, like the arena)."""
        from repro.serving.hostcache import HostTier
        return HostTier(capacity_bytes, num_shards=self.data_size,
                        staging_depth=staging_depth, integrity=integrity,
                        faults=faults, breaker=breaker, disk=disk)

    # -- device placement ---------------------------------------------------
    def batch_spec(self) -> P:
        return P(self.data_axis)

    def batch_sharding(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.batch_spec())

    def put_batch(self, x):
        """Device array with the batch (slot) dim sharded over ``data``."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, self.batch_sharding())

    def put_paged(self, cfg, paged):
        """Place a paged-cache pytree: pool/state leading dims over ``data``
        (see ``sharding.rules.paged_cache_shardings``)."""
        if self.mesh is None:
            return paged
        sh = self.paged_shardings(cfg, paged)
        return jax.tree.map(jax.device_put, paged, sh,
                            is_leaf=lambda x: isinstance(x, NamedSharding))

    def paged_shardings(self, cfg, paged):
        """NamedSharding pytree for the paged cache, or None without a mesh.
        Admission-path jits that write into sub-pools with GLOBAL pool ids —
        row-local prefill, the sequence-migration block copy — run as plain
        GSPMD programs and pin their output back to this placement, so the
        pool never silently decays to replicated; cross-shard traffic there
        is acceptable because none of it is on the round hot path."""
        if self.mesh is None:
            return None
        from repro.sharding.rules import paged_cache_shardings
        return paged_cache_shardings(cfg, paged, self.mesh,
                                     data_axis=self.data_axis)

    # -- program wrapping ---------------------------------------------------
    def wrap_round(self, fn, paged_specs, n_batch_in: int, n_batch_out: int):
        """Map the round step over the data axis: shards see local rows,
        local tables, and their local block sub-pool. ``fn`` signature is
        ``(params, paged, *batch_args) -> (paged, *batch_outs)``;
        ``paged_specs`` is the PartitionSpec pytree for the paged cache
        (``TransformerLM.paged_partition_specs``). Identity without a mesh.
        """
        if self.mesh is None:
            return fn
        d = self.batch_spec()
        return jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(P(), paged_specs) + (d,) * n_batch_in,
            out_specs=(paged_specs,) + (d,) * n_batch_out,
            axis_names={self.data_axis}, check_vma=False)
