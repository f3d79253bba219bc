"""`ServingEngine`: paged predictive-sampling serving runtime (DESIGN.md §6-10).

Subsumes the seed ``ContinuousBatcher`` (kept as a thin alias in
``repro.engine.scheduler``): requests are admitted from a priority/deadline
queue into free slots of a fixed-width batch, every verify round advances
each sequence by its own accept length, and finished sequences free their
slot and blocks immediately. What's new over the dense batcher:

* **Paged KV cache** — attention K/V lives in fixed-size blocks of a shared
  physical pool (``TransformerLM.init_paged_cache``); verify rounds and
  prefill decode *through the block tables* (``decode_window_paged`` /
  DESIGN.md §9): each layer writes its window K/V into physical blocks and
  attends via the paged flash-decode Pallas kernel (TPU) or the gather-view
  exact fallback (CPU). No dense attention K/V view of the whole cache is
  built on the round hot path — ``paged_attention=False`` restores the
  legacy gather/scatter round-trip (kept as the benchmark baseline).
  Admission allocates blocks instead of zeroing a whole cache row.
* **Mesh sharding** — a ``ServingTopology`` splits the batch slots and the
  physical pool into per-data-shard halves; the verify round runs under
  shard_map manual over "data", so each shard decodes its rows against its
  own sub-pool through *shard-local* block tables (zero collectives on the
  round hot path; DESIGN.md §10). Admission routes requests to the shard
  with the most block headroom. Tokens are bit-identical to the
  single-device engine (placement-independent noise streams).
* **Prefix cache** — full prompt blocks are content-hashed (chained keys);
  admissions sharing a prompt prefix point their tables at the cached blocks
  and skip recomputing them. Under a mesh the cache is per-shard (blocks
  never cross shards).
* **Host cache tier** (DESIGN.md §13) — a bounded host-memory arena behind
  the device prefix cache: evicted prefix blocks spill D2H and re-admit via
  async double-buffered H2D staging overlapped with prefill; parked
  sequences dedup their shared prompt blocks through the same arena; and
  recurrent-state snapshots checkpointed at block boundaries give
  ssm/rwkv/hybrid stacks prefix hits for the first time (their per-slot
  state is un-paged, so without the tier they always prefill — see
  ``_has_recurrent`` and the ``kv_prefix``/``rec_prefix`` split).
  Everything tier-related is admission-path host work: the verify-round
  jaxpr/HLO is untouched.
* **Row-local chunked prefill** — an admitted row prefills through batch-1
  windows over its own blocks; nothing scales with the batch width.
* **Device-resident verify rounds** — a verify round is a SINGLE device
  dispatch (the fused paged kernel commits window K/V as an aliased
  epilogue — no standalone scatter before the pallas_call), and up to
  ``rounds_per_sync`` rounds run inside one ``lax.while_loop`` dispatch
  between host syncs: the host pulls one packed (B, 4) stats array per
  loop instead of ``n``/``cand`` every round (DESIGN.md §11). Under a mesh
  each shard's loop stops on its own rows — no cross-shard collective.
* **Adaptive speculation** — the verify window W is retuned per host sync
  from the observed accept-length EWMA (``AdaptiveWindowController``),
  bounded to powers of two in ``[1, w_max]`` so at most ``log2(w_max)+1``
  round shapes compile; the loop runs at fixed W, so the sync IS the
  retune boundary.
* **Donated round buffers** — the physical pool and per-slot device state
  are dead the moment a round returns their successors, so the jitted round
  and prefill steps donate them (``donate_argnums``): XLA updates the pool
  in place instead of holding two full copies live per round
  (``donate=False`` restores the copying behaviour for A/B measurement).
* **Saturation-safe scheduling** (DESIGN.md §12) — admission scans a
  bounded ``lookahead`` window past an unroutable head (with an aging bound
  so the head cannot starve); a queued higher-priority request may
  **preempt** the lowest-priority running slot below a progress floor —
  its live block contents are spilled to a host-side parking list and it
  is requeued for *exact* resume (still-valid prefix blocks re-hit, the
  ``n``/``cand`` snapshot restored, tokens bitwise-identical to an
  uninterrupted run); and admission may **rebalance** a mesh by migrating
  a live sequence's blocks between shard sub-pools (device block copy +
  one table-row re-upload + per-slot state move — bit-exact by
  construction, since tokens and noise streams are placement-independent)
  when one shard's pool is exhausted while another has headroom.
* **Fault isolation** (DESIGN.md §14) — the engine fails *per request*,
  never per process: submit-time validation rejects malformed requests with
  a structured ``RequestError``; a per-row health flag folded into the
  packed sync stats (non-finite logits, stuck progress) quarantines only
  the offending slot — its blocks are released, the error attached, and
  every other row of the same batch stays bitwise identical to a fault-free
  run (poison is injected at the LOGITS level, so cache contents stay
  finite and row-local); host-side faults (allocation failures, corrupt or
  tripped host-tier entries, staging drops) unwind to the request that hit
  them, with bounded retries (``request_retries``) and fresh noise streams
  for quarantined rows; ``cancel(uid)`` removes a request wherever it
  currently lives (queued, parked, running); ``max_request_seconds`` /
  ``max_request_rounds`` bound runaway requests. All of it is scriptable
  through a deterministic ``FaultPlan`` (``repro.serving.faults``).
* **Telemetry** — per-request latency/accept/ARM-call counters, deadline
  (SLO) misses — including expiries detected while still queued/parked —
  preemption/migration/aging counters, and engine gauges exported as plain
  dicts (``EngineMetrics``); host spans of each step's phases (admission,
  prefix lookup, block allocation and spills, prefill and round dispatch,
  sync, harvest) in a bounded ``SpanLog``, and named scopes
  (``serve.round_loop``, ``serve.prefill``) on the device programs, both
  visible in a profile.

Exactness: every path emits tokens bit-identical to a per-request
``PredictiveSampler.generate`` run with the same eps key and noise-stream id
(``Request.seq_id``) — asserted in tests/serving/test_engine.py and, for the
mesh paths, tests/serving/test_mesh_engine.py.
"""
from __future__ import annotations

import itertools
import json
import os
import time
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import maybe_check
from repro.engine.spec_decode import GenState, make_eps_fn, verify_round
from repro.kernels import resolve_interpret
from repro.models.transformer import PagedView, TransformerLM
from repro.serving.admission import (AdmissionQueue, Request, StagedEntry,
                                     pack_staged_descriptors, pow2_at_most,
                                     prefill_chunks)
from repro.serving.adaptive import (AdaptiveWindowController,
                                    RoundsPerSyncController)
from repro.serving.blocks import (ShardedBlockPool, StagingLedger,
                                  chain_hashes)
from repro.serving.faults import (CircuitBreaker, FaultPlan, RequestError,
                                  kill_point)
from repro.serving.hostcache import DiskTier
from repro.serving.journal import RequestJournal
from repro.serving.metrics import EngineMetrics, default_span_log
from repro.serving.topology import ServingTopology

_ENGINE_IDS = itertools.count()     # the small integer naming each engine


def _has_recurrent(cfg) -> bool:
    return any(m in ("mamba", "rwkv") or f == "rwkv_cmix"
               for m, f in cfg.layer_specs())


@dataclass
class ParkedSequence:
    """Host-side parking payload of a preempted slot (DESIGN.md §12, §13).

    Everything an exact resume needs: the accepted-token row and the
    ``n``/``cand`` snapshot (candidates gate only acceptance, never token
    values — restoring them keeps even the *round count* identical to an
    uninterrupted run), plus the contents of the ``nb_live`` blocks that
    hold positions ``[0, n-1)`` (position ``n-1`` onward is rewritten by
    the next verify window, so those blocks need no spill).

    With a host tier the payload is split (§13): the victim's full prompt
    blocks live ONCE in the tier's shared ``kv`` namespace, refcount-pinned
    under ``kv_keys`` — N victims of a shared prefix pin the same entries
    instead of storing N copies — and only the *private* remainder (rows of
    the tail blocks ``[len(kv_keys), nb_live)`` preceded by the recurrent
    state row) is parked per victim: in the arena (``in_arena``) when it
    fits, raw in ``private`` otherwise. Without a tier, ``payload`` is the
    legacy cache-shaped pytree: attention leaves carry the gathered pool
    rows in table order, recurrent leaves the slot's state snapshot."""
    n: int
    tokens: np.ndarray           # (max_len,) accepted-token row
    cand: np.ndarray             # (W_max,) verify-window snapshot
    nb_live: int                 # leading owned blocks whose contents matter
    payload: Optional[dict] = None   # legacy host pytree (no host tier)
    kv_keys: tuple = ()          # arena-pinned chain keys, blocks [0, len)
    n_rec: int = 0               # leading private arrays = recurrent row
    rows_per_block: int = 0      # arrays per tail block in the private part
    in_arena: bool = False       # private part parked under ("park", uid)
    private: Optional[list] = None   # raw fallback when the arena was full
    shard: int = 0               # tier kv partition the pins live under
    #                              (resume may land on a different shard)
    cold: bool = False           # checkpoint-restored park (DESIGN.md §16):
    #                              no live payload or pins exist in THIS
    #                              process — resume rebuilds through the
    #                              disk-tier fall-through + re-prefill and
    #                              never consumes a payload


class ServingEngine:
    def __init__(self, cfg, params, *, batch: int, window_max: int = 8,
                 max_len: int = 256, eps_key=None, eps_fn=None,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 adaptive: bool = True, window_init: int = 0,
                 prefix_cache: bool = True, prefill_chunk: int = 64,
                 use_forecast_heads: bool = False,
                 use_verify_kernel: bool = False,
                 paged_attention: bool = True,
                 use_attention_kernel: Optional[bool] = None,
                 topology: Optional[ServingTopology] = None,
                 donate: bool = True, rounds_per_sync: int = 4,
                 lookahead: int = 8, max_head_bypass: int = 16,
                 preempt: bool = True, preempt_floor: float = 0.75,
                 rebalance: bool = True,
                 host_cache_mb: Optional[float] = None, host_tier=None,
                 request_retries: int = 0,
                 max_request_seconds: Optional[float] = None,
                 max_request_rounds: Optional[int] = None,
                 integrity_checks: bool = True,
                 faults: Optional[FaultPlan] = None,
                 staging_slots: int = 0,
                 adaptive_rounds: Optional[bool] = None,
                 host_prefetch: Optional[bool] = None,
                 prefetch_budget: int = 4,
                 durable_dir: Optional[str] = None,
                 journal_fsync_every: int = 1,
                 disk_tier: bool = True,
                 disk_cache_mb: Optional[float] = None):
        assert block_size >= 1, f"block_size must be >= 1, got {block_size}"
        assert window_max >= 1, f"window_max must be >= 1, got {window_max}"
        assert rounds_per_sync >= 1, rounds_per_sync
        assert staging_slots >= 0, staging_slots
        assert prefetch_budget >= 0, prefetch_budget
        assert lookahead >= 1, lookahead
        assert max_head_bypass >= 0, max_head_bypass
        assert 0.0 <= preempt_floor <= 1.0, preempt_floor
        self.cfg = cfg
        self.params = params
        self.B = batch
        self.W_max = window_max
        self.max_len = max_len
        self.block_size = block_size
        # pow2 normalization keeps the "log2(max_chunk)+1 compiled prefill
        # widths" guarantee honest for non-pow2 user values (48 -> 32)
        assert prefill_chunk >= 1, prefill_chunk
        self.prefill_chunk = pow2_at_most(prefill_chunk)
        self.use_forecast_heads = (use_forecast_heads
                                   and "forecast" in params
                                   and cfg.forecast_horizon > 0)
        self.use_verify_kernel = use_verify_kernel
        # paged_attention: decode through block tables (no dense K/V view on
        # the round hot path). The Pallas kernel is the compiled TPU fast
        # path; elsewhere the default is the gather-view fallback, which is
        # bit-exact vs the dense engine (resolve_interpret's dispatch).
        # GSPMD cannot partition a compiled Mosaic kernel, and a mesh
        # engine's prefill (and a tensor-parallel round) is a GSPMD program:
        # on a mesh every Pallas call (the gather-view path's aliased
        # writeback included) runs in interpret mode, as plain XLA ops, and
        # attention defaults to the gather-view path.
        self.paged_attention = paged_attention
        on_mesh = topology is not None and topology.mesh is not None
        self.kernel_interpret = True if on_mesh else None
        if use_attention_kernel is None:
            use_attention_kernel = not on_mesh and not resolve_interpret(None)
        self.use_attention_kernel = use_attention_kernel
        # donate the pool + per-slot state into the jitted round/prefill
        # steps (their previous values are dead once the step returns)
        self.donate = donate
        # device-resident rounds: up to this many verify rounds run inside
        # one dispatch (lax.while_loop) between host syncs; 1 = host-driven
        self.rounds_per_sync = rounds_per_sync
        # device-resident continuous batching (DESIGN.md §15): admission
        # pre-stages up to ``staging_slots`` queued requests PER SHARD into
        # spare pool blocks; inside the round loop a freed (or quarantined)
        # row adopts the next staged descriptor without a host sync.
        # ``adaptive_rounds`` replaces the binary ``k = 1 if queue`` sync
        # heuristic with a controller retuned from observed idle row-rounds;
        # it defaults on exactly when staging is on (without adoption a long
        # loop under backlog just strands freed rows).
        self.staging_slots = staging_slots
        # the controller's idle signal only exists in the staged stats ABI,
        # so adaptivity is meaningful (and allowed) only with staging on
        self.adaptive_rounds = (staging_slots > 0 if adaptive_rounds is None
                                else bool(adaptive_rounds)
                                and staging_slots > 0)
        self.rounds_ctrl = RoundsPerSyncController(
            k_max=rounds_per_sync, enabled=self.adaptive_rounds)
        # host-tier prefix prefetch for QUEUED requests (§15 satellite):
        # restage their host-resident prefix blocks through the staging
        # ring while they wait instead of at admission
        self.host_prefetch = (staging_slots > 0 if host_prefetch is None
                              else bool(host_prefetch))
        self.prefetch_budget = prefetch_budget
        # saturation-safe scheduling (DESIGN.md §12): admission lookahead
        # window, head-aging bound, priority preemption (+ progress floor:
        # slots past this generated fraction are never evicted), and
        # cross-shard rebalancing by sequence migration
        self.lookahead = lookahead
        self.max_head_bypass = max_head_bypass
        self.preempt = preempt
        self.preempt_floor = preempt_floor
        self.rebalance = rebalance
        # fault isolation (DESIGN.md §14): bounded re-admission after
        # retryable failures, runaway-request bounds, and the deterministic
        # fault-injection plan (defaults to REPRO_FAULT_PLAN — the CI chaos
        # job's hook — so production code paths need no test shims)
        assert request_retries >= 0, request_retries
        self.request_retries = request_retries
        self.max_request_seconds = max_request_seconds
        self.max_request_rounds = max_request_rounds
        self.faults = faults if faults is not None else FaultPlan.from_env()
        self.eps_fn = eps_fn if eps_fn is not None else make_eps_fn(
            eps_key if eps_key is not None else jax.random.PRNGKey(0),
            cfg.vocab)

        # ---- topology (slot ranges + block sub-pools per data shard) -----
        self.topo = topology if topology is not None else ServingTopology()
        D = self.topo.data_size
        self.B_local = self.topo.slots_per_shard(batch)

        # ---- paged cache ------------------------------------------------
        self.nb = -(-(max_len + window_max) // block_size)  # table width
        if num_blocks is None:
            # per shard: full occupancy + slack so unreferenced prefix
            # blocks survive
            num_blocks = 1 + self.B_local * self.nb + 2 * self.nb
        # ``num_blocks`` is PER DATA SHARD; the device pool holds D of them
        self.pool = ShardedBlockPool(D, num_blocks, block_size)
        self.paged = self.topo.put_paged(cfg, TransformerLM.init_paged_cache(
            cfg, batch, D * num_blocks, block_size, dtype=cfg.param_dtype))
        self._paged_specs = TransformerLM.paged_partition_specs(
            cfg, self.paged, data_axis=self.topo.data_axis)
        # block tables hold SHARD-LOCAL ids (each shard's sink is local 0);
        # host-side code converts to global pool ids via the shard offset
        self.tables = np.zeros((batch, self.nb), np.int32)
        self.owned: list[list[int]] = [[] for _ in range(batch)]

        # ---- durability layer (DESIGN.md §16) ---------------------------
        # ``durable_dir`` roots the crash-safety state: ``disk/`` (the tier
        # below the arena), ``journal.wal`` (the write-ahead request
        # journal), ``checkpoint.json`` (the scheduler snapshot written at
        # sync boundaries). None = volatile engine, byte-for-byte the old
        # behaviour. ``disk_tier=False`` (--no-disk-tier) keeps journal +
        # checkpoint but drops the prefix spill (restarts re-prefill).
        assert journal_fsync_every >= 1, journal_fsync_every
        self.durable_dir = durable_dir
        self.journal = None
        self._ckpt_path = None
        self.disk = None
        if durable_dir is not None:
            if disk_tier:
                dmb = 1024.0 if disk_cache_mb is None else float(disk_cache_mb)
                self.disk = DiskTier(os.path.join(durable_dir, "disk"),
                                     int(dmb * 2 ** 20), faults=self.faults,
                                     breaker=CircuitBreaker())
            self.journal = RequestJournal(
                os.path.join(durable_dir, "journal.wal"),
                fsync_every=journal_fsync_every, faults=self.faults)
            self._ckpt_path = os.path.join(durable_dir, "checkpoint.json")

        # ---- host cache tier (DESIGN.md §13) ----------------------------
        # One byte-budgeted arena behind the device prefix cache: spilled
        # KV blocks, parked-sequence payloads, recurrent-state snapshots.
        # ``host_cache_mb=0`` (or --no-host-cache) disables it; unset falls
        # back to REPRO_HOST_CACHE_MB, then 256 MiB.
        if host_tier is not None:
            self.tier = host_tier
        else:
            mb = host_cache_mb
            if mb is None:
                mb = float(os.environ.get("REPRO_HOST_CACHE_MB", 256))
            self.tier = (self.topo.host_tier(
                int(mb * 2 ** 20), integrity=integrity_checks,
                faults=self.faults, breaker=CircuitBreaker(),
                disk=self.disk)
                if mb > 0 else None)
        if self.faults is not None:
            # the 'alloc' seam: injected block-allocation failures surface
            # as the MemoryError a genuinely exhausted pool would raise
            self.pool.set_fault_hook(lambda: self.faults.fire("alloc"))

        # prefix-cache enablement is split per state kind: attention KV
        # blocks are paged and shareable as before (``kv_prefix``), while a
        # prefix hit for a recurrent stack additionally needs the
        # post-prefix per-slot state — un-paged, so only reachable through
        # the tier's recurrent-state snapshots (``rec_prefix``; without a
        # tier, recurrent archs always prefill, as before)
        has_rec = _has_recurrent(cfg)
        self.has_attn = any(m not in ("mamba", "rwkv")
                            for m, _ in cfg.layer_specs())
        self.kv_prefix = prefix_cache and not has_rec
        self.rec_prefix = prefix_cache and has_rec and self.tier is not None
        # device KV blocks are registered/looked-up whenever the arch has
        # attention layers to fill them (hybrids included under rec_prefix)
        self._kv_share = self.kv_prefix or (self.rec_prefix and self.has_attn)
        self.pool.set_spill_hook(self._make_spill_hook)

        # ---- control / telemetry ---------------------------------------
        self.controller = AdaptiveWindowController(
            w_max=window_max, w_init=window_init, enabled=adaptive)
        self.metrics = EngineMetrics()
        # host spans of each step's phases go to the process's span log
        self._engine_no = next(_ENGINE_IDS)
        self._step_no = 0
        self.queue = AdmissionQueue()
        self.slots: list[Optional[Request]] = [None] * batch
        self.done: list[Request] = []
        self.target = np.zeros(batch, np.int64)
        # worst-case block need reserved per slot at admission (run-to-
        # completion guarantee: lazy growth may never exhaust the pool)
        self.reserved = np.zeros(batch, np.int64)
        # host mirror of each slot's accepted length, refreshed from the
        # packed stats at every sync (preemption progress floor + parking)
        self.n_host = np.ones(batch, np.int64)
        # parked (preempted) sequences by request uid, awaiting exact resume
        self.parked: dict[int, ParkedSequence] = {}
        self._last_rounds_exec = 0
        # staging area (§15): per-shard FIFO of pre-staged entries, a
        # ledger capping their block claims to spare headroom (staging can
        # never starve resident reservations), and the prefetched host-tier
        # rows of still-queued requests ``uid -> (shard, {key: dev rows})``
        self.staged: list[list[StagedEntry]] = [[] for _ in range(D)]
        self.ledger = StagingLedger(staging_slots)
        self._prefetched: dict[int, tuple[int, dict]] = {}

        # ---- per-slot device state (slot dim sharded over "data") -------
        self.tokens = self.topo.put_batch(jnp.zeros((batch, max_len),
                                                    jnp.int32))
        self.n = self.topo.put_batch(jnp.ones((batch,), jnp.int32))
        # ^ cleared-row sentinel n=1
        self.cand = self.topo.put_batch(jnp.zeros((batch, window_max),
                                                  jnp.int32))
        # noise-stream ids: host mirror + cached upload (the staged round
        # ABI loop-carries the device copy so in-loop adoption can swap a
        # row's stream; the host mirror stays authoritative for admission)
        self.seq_ids = np.zeros(batch, np.int32)
        # per-slot prompt length: rows at n >= plen behave identically to
        # the legacy engine (forced-acceptance prefill is a provable no-op
        # there); only rows adopted mid-loop ever see n < plen
        self.plen = np.zeros(batch, np.int64)
        # per-slot poison mask (§14): rows whose noise stream is scripted in
        # ``faults.poison_streams`` get their verify-round logits
        # NaN-replaced on device — the injection point of the quarantine
        # path. All zeros (the common case) is a bit-exact no-op.
        self.poison = np.zeros(batch, np.int32)
        # cached device copies of host-owned admission state; invalidated
        # only when the host actually mutates them (admission, slot clear,
        # table growth) instead of re-uploading every round
        self._tables_dev = None
        self._target_dev = None
        self._poison_dev = None
        self._seq_dev = None
        self._plen_dev = None

        self._round_fns: dict[tuple[int, int], callable] = {}
        self._prefill_fns: dict[int, callable] = {}
        self._copy_fn = None

    # -- seed-API compatibility -------------------------------------------
    @property
    def state(self):
        """Seed ``ContinuousBatcher`` exposed ``state.rounds``; preserved."""
        return SimpleNamespace(rounds=self.metrics.rounds, n=self.n,
                               tokens=self.tokens)

    @property
    def prefix_enabled(self) -> bool:
        """Any prefix reuse active (device KV and/or tiered recurrent)."""
        return self.kv_prefix or self.rec_prefix

    def _validate(self, req: Request) -> Optional[RequestError]:
        """Submit-time validation (DESIGN.md §14): reject malformed or
        unservable requests *before* they own a slot, with a structured
        reason — never an assert five layers down. Token range is checked
        on VALUES (prompts arrive as any integral-valued array; the engine
        casts to int32 at admission)."""
        prompt = np.asarray(req.prompt)
        if prompt.size < 1:
            return RequestError("empty_prompt", "prompt holds no tokens")
        if req.new_tokens <= 0:
            return RequestError("bad_new_tokens",
                                f"new_tokens={req.new_tokens}")
        if prompt.size + req.new_tokens > self.max_len:
            return RequestError(
                "too_long", f"{prompt.size} prompt + {req.new_tokens} new "
                f"> max_len={self.max_len}")
        lo, hi = int(prompt.min()), int(prompt.max())
        if lo < 0 or hi >= self.cfg.vocab:
            return RequestError(
                "token_out_of_range",
                f"tokens span [{lo}, {hi}], vocab={self.cfg.vocab}")
        cap = self.pool.blocks_per_shard - 1      # minus the reserved sink
        if self._worst_case_blocks(req) > cap:
            return RequestError(
                "over_capacity", f"worst case {self._worst_case_blocks(req)}"
                f" blocks > pool capacity {cap}/shard")
        return None

    def submit(self, req: Request) -> bool:
        """Validate and enqueue. Returns False — with ``req.error`` set and
        the request delivered through ``done`` — on rejection."""
        err = self._validate(req)
        if err is not None:
            req.error = err
            req.submit_time = time.monotonic()
            req.finish_time = req.submit_time
            self.metrics.requests_rejected += 1
            self.done.append(req)
            return False
        self.queue.push(req)
        # journal AFTER push: the queue pinned the arrival rank the record
        # durable-izes; with fsync_every=1 the submit is on media before
        # this returns — an accepted request survives any later crash
        self._journal("submit", uid=int(req.uid),
                      prompt=[int(t) for t in
                              np.asarray(req.prompt).ravel()],
                      new_tokens=int(req.new_tokens),
                      priority=int(req.priority), deadline=req.deadline,
                      noise_seed=req.noise_seed, rank=int(req._seq))
        return True

    def _span(self, name: str, uid: Optional[int] = None):
        """A span of this engine's host work in the process's span log,
        stamped with the engine and the current step (``uid`` on request
        spans)."""
        return default_span_log().span(name, step=self._step_no, uid=uid,
                                       engine=self._engine_no)

    def _journal(self, type: str, **fields):
        """Append one lifecycle record when a journal is configured
        (DESIGN.md §16); a no-op for volatile engines."""
        if self.journal is not None:
            self.journal.append(type, **fields)

    # -- jitted steps -------------------------------------------------------
    def _round_loop_fn(self, W: int, k: int):
        """Up to ``k`` verify rounds in ONE device dispatch. The round body
        decodes through the block tables — the fused paged kernel commits
        the window K/V into its physical blocks as an aliased epilogue while
        attention streams the pool (one pallas_call per layer, no standalone
        window scatter; per-round HBM traffic independent of pool size).
        Legacy mode is the dense round-trip: gather the whole view, decode,
        write the window span back through the same aliased writeback.

        A ``lax.while_loop`` re-runs the body until every local row is done
        or ``k`` rounds have run (the window-retune boundary): the host
        syncs one small packed stats array per *loop*, not per round —
        (R, 5) int32 ``[accepted, rounds_active, new_length, loop_rounds,
        bad]`` (DESIGN.md §11, §14). ``bad`` is the sticky per-row health
        flag the quarantine path reads: bit 0 = the row produced non-finite
        logits while active (poisoned stream or genuine numeric blowup),
        bit 1 = the row made no progress over an active round. Rows gone
        bad stop counting toward the loop condition — NaNs are row-local
        (logits-level injection; cache contents stay finite), so freezing a
        bad row leaves every healthy row bitwise identical to a fault-free
        run, and inactive rows remain no-ops as before.

        With ``staging_slots > 0`` the loop additionally performs **in-loop
        slot adoption** (DESIGN.md §15): the body opens with a device-side
        free-row scan — rows done or quarantined — that adopts the next
        staged descriptors (FIFO) into those rows: table-row swap, staged
        prompt buffer, fresh noise stream, and forced-acceptance prefill at
        the same verify widths (``prompt_len``), so occupancy stays
        saturated with ZERO extra host pulls. The ABI grows to loop-carry
        everything adoption mutates (tables/seq_ids/target/poison/plen) and
        returns per-descriptor episode stats plus the displaced token rows;
        the packed stats widen to (R, 7) ``[..., gen_rounds, idle_rounds]``.
        Every adoption-scan write is a rank-2 scatter into the small
        descriptor-keyed outputs — the pool itself is only ever touched by
        the same verify-round writeback, so the zero-pool-ranked-scatter and
        zero-collective HLO gates hold unchanged. With ``staging_slots ==
        0`` the legacy 9-arg program below is built bit-for-bit unchanged
        (cached host uploads stay identity-stable across steps).

        Under a mesh topology the whole loop runs shard_map-manual over
        "data": each shard sees its local rows, its local tables, and its
        local block sub-pool, and — crucially — its while_loop stops on its
        OWN rows, so the stop condition needs no cross-shard collective
        (shards may run different trip counts; the compiled HLO stays
        collective-free). The old pool and per-slot state are donated (dead
        after the loop), so XLA updates the pool in place round over round
        instead of copying it."""
        if (W, k) not in self._round_fns:
            if self.staging_slots > 0:
                self._round_fns[(W, k)] = self._build_staged_round(W, k)
                return self._round_fns[(W, k)]
            cfg = self.cfg

            @jax.named_scope("serve.round_loop")
            def fn(params, paged, tables, tokens, n, cand, seq_ids, target,
                   poison):
                R = tokens.shape[0]          # rows on this shard (B/D)
                rows = jnp.arange(R)

                def one_round(paged, tokens, n, cand):
                    if self.paged_attention:
                        cache = paged
                        pv = self._view(tables, rows)
                    else:
                        cache = TransformerLM.gather_paged(cfg, paged,
                                                           tables, rows)
                        pv = None
                    st = GenState(tokens, n, cand[:, :W], cache,
                                  jnp.zeros((), jnp.int32),
                                  jnp.zeros((R,), jnp.int32),
                                  jnp.zeros((R,), jnp.int32), seq_ids)
                    st2, rstats = verify_round(
                        params, cfg, self.eps_fn, st, target,
                        use_forecast_heads=self.use_forecast_heads,
                        use_verify_kernel=self.use_verify_kernel, paged=pv,
                        poison=poison)
                    if self.paged_attention:
                        paged2 = st2.cache
                    else:
                        active = n < target
                        paged2 = TransformerLM.scatter_paged(
                            cfg, paged, st2.cache, tables, rows,
                            jnp.maximum(n - 1, 0), W, active,
                            interpret=self.kernel_interpret)
                    cand2 = jnp.zeros_like(cand).at[:, :W].set(st2.cand)
                    return paged2, st2.tokens, st2.n, cand2, rstats

                def cond(carry):
                    _, _, n_c, _, _, _, bad, r = carry
                    return (r < k) & jnp.any((n_c < target) & (bad == 0))

                def body(carry):
                    paged_c, tokens_c, n_c, cand_c, acc, act_rounds, bad, \
                        r = carry
                    active = (n_c < target).astype(jnp.int32)
                    n_prev = n_c
                    paged_c, tokens_c, n_c, cand_c, rstats = one_round(
                        paged_c, tokens_c, n_c, cand_c)
                    # consume the §11 per-round stats ABI: col 0 = accepted,
                    # col 3 = non-finite logits; sticky health bits (§14)
                    stuck = active * (n_c == n_prev).astype(jnp.int32)
                    bad = bad | (active * rstats[:, 3]) | (stuck << 1)
                    return (paged_c, tokens_c, n_c, cand_c,
                            acc + rstats[:, 0], act_rounds + active, bad,
                            r + 1)

                init = (paged, tokens, n, cand, jnp.zeros((R,), jnp.int32),
                        jnp.zeros((R,), jnp.int32),
                        jnp.zeros((R,), jnp.int32), jnp.zeros((), jnp.int32))
                (paged2, tokens2, n2, cand2, acc, act_rounds, bad, r) = \
                    jax.lax.while_loop(cond, body, init)
                stats = jnp.stack(
                    [acc, act_rounds, n2,
                     jnp.broadcast_to(r, (R,)), bad], axis=1)
                return paged2, tokens2, n2, cand2, stats

            wrapped = self.topo.wrap_round(fn, self._paged_specs,
                                           n_batch_in=7, n_batch_out=4)
            # donate pool + tokens/n/cand (dead after the loop); tables,
            # seq_ids and target are cached host-owned uploads — kept alive
            donate = (1, 3, 4, 5) if self.donate else ()
            self._round_fns[(W, k)] = jax.jit(wrapped, donate_argnums=donate)
        return self._round_fns[(W, k)]

    def _build_staged_round(self, W: int, k: int):
        """The ``staging_slots > 0`` round-loop program (DESIGN.md §15).

        ABI: ``fn(params, paged, tables, tokens, n, cand, seq_ids, target,
        poison, plen, d_valid, d_tables, d_tokens, d_n, d_target, d_seq,
        d_poison, d_plen, q_more) -> (paged, tables, tokens, n, cand,
        seq_ids, target, poison, plen, stats, adopt_stats, out_tokens)``.
        The d_* descriptor arrays hold this dispatch's staged entries,
        shard-major ``[shard * S + i]`` (S = staging_slots per shard, FIFO
        within a shard); they are uploaded fresh per dispatch and consumed
        in order by the in-loop adoption scan. ``q_more`` is the per-shard
        starvation-exit flag: 1 while the host holds backlog beyond the
        staged set, letting the cond sync early once a row frees with the
        area drained (see ``cond``). Outputs keyed by descriptor:
        ``adopt_stats`` (S, 6) int32 ``[local_row, n, accepted,
        rounds_active, bad, gen_rounds]`` of the episode the adoption
        DISPLACED (-1 rows = descriptor not adopted), and ``out_tokens``
        (S, max_len) the displaced token row — the finished sequence whose
        slot was recycled mid-loop. The loop keeps running while any row is
        live OR descriptors remain unconsumed (adopted rows always start at
        ``n < target``, so every iteration makes progress toward one of the
        two bounds; ``r < k`` caps the trip count regardless)."""
        cfg = self.cfg

        @jax.named_scope("serve.round_loop")
        def fn(params, paged, tables, tokens, n, cand, seq_ids, target,
               poison, plen, d_valid, d_tables, d_tokens, d_n, d_target,
               d_seq, d_poison, d_plen, q_more):
            R = tokens.shape[0]          # rows on this shard (B/D)
            S = d_valid.shape[0]         # staged descriptors on this shard
            max_len = tokens.shape[1]
            Wm = cand.shape[1]
            rows = jnp.arange(R)
            count = jnp.sum(d_valid)     # shard-local, no collective

            def one_round(paged, tokens, n, cand, tables, seq_ids, target,
                          poison, plen):
                if self.paged_attention:
                    cache = paged
                    pv = self._view(tables, rows)
                else:
                    cache = TransformerLM.gather_paged(cfg, paged,
                                                       tables, rows)
                    pv = None
                st = GenState(tokens, n, cand[:, :W], cache,
                              jnp.zeros((), jnp.int32),
                              jnp.zeros((R,), jnp.int32),
                              jnp.zeros((R,), jnp.int32), seq_ids)
                st2, rstats = verify_round(
                    params, cfg, self.eps_fn, st, target,
                    use_forecast_heads=self.use_forecast_heads,
                    use_verify_kernel=self.use_verify_kernel, paged=pv,
                    poison=poison, prompt_len=plen)
                if self.paged_attention:
                    paged2 = st2.cache
                else:
                    active = n < target
                    paged2 = TransformerLM.scatter_paged(
                        cfg, paged, st2.cache, tables, rows,
                        jnp.maximum(n - 1, 0), W, active,
                        interpret=self.kernel_interpret)
                cand2 = jnp.zeros_like(cand).at[:, :W].set(st2.cand)
                return paged2, st2.tokens, st2.n, cand2, rstats

            def cond(carry):
                n_c, target_c, bad = carry[3], carry[6], carry[11]
                m, r = carry[14], carry[15]
                live = jnp.any((n_c < target_c) & (bad == 0))
                # starvation exit: a freed row with the staging area drained
                # while the host still holds backlog (q_more) means the
                # right move is to sync NOW and let the host restage —
                # idling to the k bound is the one cost adoption can't fix.
                # (After at least one round, so a dispatch always makes
                # progress even when admission is stuck on capacity.)
                free_now = (n_c >= target_c) | (bad > 0)
                starve = ((q_more[0] > 0) & (m >= count)
                          & jnp.any(free_now) & (r > 0))
                return (r < k) & (live | (m < count)) & ~starve

            def body(carry):
                (paged_c, tables_c, tokens_c, n_c, cand_c, seq_c, target_c,
                 poison_c, plen_c, acc, act, bad, gen, idle, m, r, astats,
                 otok) = carry
                # ---- in-loop adoption scan: freed/quarantined rows pull
                # the next staged descriptors, FIFO, without a sync -------
                free = (n_c >= target_c) | (bad > 0)
                rank = jnp.cumsum(free.astype(jnp.int32)) - 1
                desc = m + rank              # FIFO: row order breaks ties
                take = free & (desc < count)
                di = jnp.where(take, desc, S)    # S = scatter-drop sentinel
                # displaced episodes, keyed by descriptor (rank-2 scatters:
                # the pool never appears on the left of an adoption write)
                otok = otok.at[di].set(tokens_c, mode="drop")
                ep = jnp.stack([rows.astype(jnp.int32), n_c, acc, act, bad,
                                gen], axis=1)
                astats = astats.at[di].set(ep, mode="drop")
                src = jnp.clip(desc, 0, S - 1)
                tk = take[:, None]
                tokens_c = jnp.where(tk, d_tokens[src], tokens_c)
                tables_c = jnp.where(tk, d_tables[src], tables_c)
                n_c = jnp.where(take, d_n[src], n_c)
                seq_c = jnp.where(take, d_seq[src], seq_c)
                target_c = jnp.where(take, d_target[src], target_c)
                poison_c = jnp.where(take, d_poison[src], poison_c)
                plen_c = jnp.where(take, d_plen[src], plen_c)
                # adopted verify window: slots inside the prompt carry the
                # true prompt tokens (they source the K/V writes and the
                # forced matches); slot 0 = token at n0-1 is always covered
                p = (d_n[src] - 1)[:, None] + jnp.arange(Wm)[None, :]
                ptok = jnp.take_along_axis(
                    d_tokens[src], jnp.clip(p, 0, max_len - 1), axis=1)
                a_cand = jnp.where((p <= (d_plen[src] - 1)[:, None])
                                   & (jnp.arange(Wm)[None, :] < W), ptok, 0)
                cand_c = jnp.where(tk, a_cand, cand_c)
                # fresh episode accumulators + a zeroed recurrent row (the
                # adopted sequence replays its prompt from scratch there)
                acc = jnp.where(take, 0, acc)
                act = jnp.where(take, 0, act)
                bad = jnp.where(take, 0, bad)
                gen = jnp.where(take, 0, gen)
                idle = idle + (free & ~take).astype(jnp.int32)
                m = m + jnp.sum(take.astype(jnp.int32))
                if _has_recurrent(cfg):
                    def zrec(stacked, leaf):
                        shp = [1] * leaf.ndim
                        shp[1 if stacked else 0] = R
                        return jnp.where(take.reshape(shp),
                                         jnp.zeros((), leaf.dtype), leaf)

                    paged_c = TransformerLM._map_paged(
                        cfg, (paged_c,), lambda stacked, leaf: leaf, zrec)
                # ---- verify round (adopted rows prefill-by-window via
                # forced acceptance; resident rows are bit-identical to the
                # legacy body) -------------------------------------------
                active = (n_c < target_c).astype(jnp.int32)
                n_prev = n_c
                paged_c, tokens_c, n_c, cand_c, rstats = one_round(
                    paged_c, tokens_c, n_c, cand_c, tables_c, seq_c,
                    target_c, poison_c, plen_c)
                stuck = active * (n_c == n_prev).astype(jnp.int32)
                bad = bad | (active * rstats[:, 3]) | (stuck << 1)
                # accepted counts GENERATED tokens only (forced prompt
                # accepts are prefill throughput, not generation)
                acc = acc + jnp.maximum(n_c - jnp.maximum(n_prev, plen_c), 0)
                act = act + active
                gen = gen + active * (n_c > plen_c).astype(jnp.int32)
                return (paged_c, tables_c, tokens_c, n_c, cand_c, seq_c,
                        target_c, poison_c, plen_c, acc, act, bad, gen,
                        idle, m, r + 1, astats, otok)

            z = jnp.zeros((R,), jnp.int32)
            init = (paged, tables, tokens, n, cand, seq_ids, target,
                    poison, plen, z, z, z, z, z, jnp.zeros((), jnp.int32),
                    jnp.zeros((), jnp.int32),
                    jnp.full((S, 6), -1, jnp.int32),
                    jnp.zeros((S, max_len), jnp.int32))
            (paged2, tables2, tokens2, n2, cand2, seq2, target2, poison2,
             plen2, acc, act, bad, gen, idle, m, r, astats, otok) = \
                jax.lax.while_loop(cond, body, init)
            stats = jnp.stack(
                [acc, act, n2, jnp.broadcast_to(r, (R,)), bad, gen, idle],
                axis=1)
            return (paged2, tables2, tokens2, n2, cand2, seq2, target2,
                    poison2, plen2, stats, astats, otok)

        wrapped = self.topo.wrap_round(fn, self._paged_specs,
                                       n_batch_in=17, n_batch_out=11)
        # everything loop-carried is dead after the loop; descriptor
        # uploads (10..17) are rebuilt per dispatch but tiny — not donated
        donate = tuple(range(1, 10)) if self.donate else ()
        return jax.jit(wrapped, donate_argnums=donate)

    def _contract_check(self, kind: str, fn, args) -> None:
        """§17 contract seam: under ``REPRO_CHECK_CONTRACTS=1`` every
        compiled program is checked against its named contract once at
        first dispatch (zero collectives / pool-ranked scatters / host
        callbacks / f64, donation aliasing, recompile hazard). The label
        is per-engine so the recompile registry never mixes instances;
        ``donate=False`` engines skip the aliasing rule."""
        maybe_check(kind, fn, args, label=f"{kind}@{hex(id(self))}",
                    donate=self.donate, **self._contract_exemptions())

    def _contract_exemptions(self) -> dict:
        """Arch/topology refinements of the §17 contracts for THIS engine
        (consumed by ``maybe_check``/``check_engine_round``):

        * ``tensor_parallel`` — a model axis left to GSPMD all-reduces
          partial products every layer by design and does not preserve
          the manual pool-donation aliasing, so the data-axis-only rules
          (NoCollectives, DonationAliasCovers) don't apply.
        * ``pool_scatter_shapes`` — the exact KV-pool leaf shapes
          (global, plus per-data-shard on the block axis), narrowing
          NoPoolRankedScatters from the rank-3 proxy to real pool
          writes: MoE expert-dispatch buffers and recurrent per-slot
          state rows are high-rank scatters the round runs by design,
          while any scatter shaped like the pool itself is the dense
          writeback regression the fused epilogue eliminated.
        """
        shapes = set()
        d = self.topo.data_size

        def pool(stacked, leaf):
            s = tuple(leaf.shape)
            shapes.add(s)
            ax = 1 if stacked else 0     # block axis (data-sharded)
            if d > 1 and s[ax] % d == 0:
                per_shard = list(s)
                per_shard[ax] //= d
                shapes.add(tuple(per_shard))
            return leaf

        TransformerLM._map_paged(self.cfg, (self.paged,), pool,
                                 lambda st, leaf: leaf)
        return {"tensor_parallel": bool(self.topo.auto_axes),
                "pool_scatter_shapes": frozenset(shapes)}

    def _view(self, tables, rows) -> PagedView:
        """Block-table view for a paged decode through this engine's
        attention path (kernel or gather view, compiled or interpreted)."""
        return PagedView(tables, rows, self.use_attention_kernel,
                         self.kernel_interpret)

    def _round_args(self) -> tuple:
        """Positional args of the jitted round loop, in ABI order — the one
        place that order is written down (tests and benches that drive the
        round fn directly build their calls through this). With staging
        enabled the tuple grows to the §15 ABI: ``plen`` plus the eight
        descriptor arrays of the current staging area."""
        base = (self.params, self.paged, self._tables_device(), self.tokens,
                self.n, self.cand, self._seq_device(), self._target_device(),
                self._poison_device())
        if self.staging_slots == 0:
            return base
        return base + (self._plen_device(),) + self._staged_args()

    def _staged_args(self) -> tuple:
        """Upload this dispatch's staging area as the eight shard-major
        descriptor arrays of the §15 ABI (data-sharded like the batch dim;
        rebuilt fresh per dispatch — entries come and go between syncs)."""
        packed = pack_staged_descriptors(
            self.staged, self.staging_slots, self.nb, self.max_len)
        # q_more: the starvation-exit signal — 1 while the host holds MORE
        # backlog beyond the staged set (a starved loop should sync so the
        # host can restage); 0 on the drain tail (nothing to restage, run
        # the loop out). One flag per shard (admission routes globally)
        q_more = np.full((self.topo.data_size,),
                         int(len(self.queue) > 0), np.int32)
        return tuple(self.topo.put_batch(a) for a in packed + (q_more,))

    def _prefill_fn(self, C: int):
        """Row-local chunked prefill. Runs as a plain (GSPMD) jit even under
        a mesh — a batch-1 write into one shard's sub-pool is admission-path
        work, so cross-shard traffic here is acceptable; ``table_row``
        carries GLOBAL pool ids (local id + shard offset). The old pool is
        donated, exactly like the round step."""
        if C not in self._prefill_fns:
            cfg = self.cfg

            @jax.named_scope("serve.prefill")
            def fn(params, paged, table_row, row, chunk, start):
                if self.paged_attention:
                    view = self._view(table_row, row)
                    _, _, nc = TransformerLM.decode_window_paged(
                        params, cfg, chunk, paged, view, start)
                    sel = TransformerLM.select_states(
                        cfg, nc, jnp.full((1,), C, jnp.int32))
                    return TransformerLM.adopt_states_paged(
                        cfg, paged, sel, row)
                view = TransformerLM.gather_paged(cfg, paged, table_row, row)
                _, _, nc = TransformerLM.decode_window(
                    params, cfg, chunk, view, start)
                sel = TransformerLM.select_states(
                    cfg, nc, jnp.full((1,), C, jnp.int32))
                return TransformerLM.scatter_paged(
                    cfg, paged, sel, table_row, row, start, C,
                    jnp.ones((1,), bool), interpret=self.kernel_interpret)

            kw = {}
            sh = self.topo.paged_shardings(cfg, self.paged)
            if sh is not None:
                kw["out_shardings"] = sh
            donate = (1,) if self.donate else ()
            self._prefill_fns[C] = jax.jit(fn, donate_argnums=donate, **kw)
        return self._prefill_fns[C]

    def _copy_blocks_fn(self):
        """Jitted sequence-move step: copy ``nb`` pool block rows
        ``src_ids -> dst_ids`` (GLOBAL ids; unused lanes padded with the
        sink id 0, whose gathered garbage rewrites itself — deterministic
        and never read unmasked) and move the per-slot recurrent state row
        ``src_row -> dst_row`` (zeroing the source row, like
        ``_clear_row``). One compiled shape per engine: the id vectors are
        padded to the table width ``nb``. Under a mesh this is a plain
        GSPMD jit, exactly like row-local prefill: a migration's cross-
        shard block copy is admission-path work, never on the round hot
        path, and the output is pinned back to the sub-pool placement so
        zero collectives appear in the ROUND HLO (the CI gate)."""
        if self._copy_fn is None:
            cfg = self.cfg

            def fn(paged, src_ids, dst_ids, src_row, dst_row):
                def attn(stacked, leaf):
                    if stacked:
                        return leaf.at[:, dst_ids].set(leaf[:, src_ids])
                    return leaf.at[dst_ids].set(leaf[src_ids])

                def rec(stacked, leaf):
                    if stacked:
                        moved = leaf[:, src_row]
                        return (leaf.at[:, dst_row].set(moved)
                                .at[:, src_row].set(jnp.zeros_like(moved)))
                    moved = leaf[src_row]
                    return (leaf.at[dst_row].set(moved)
                            .at[src_row].set(jnp.zeros_like(moved)))

                return TransformerLM._map_paged(cfg, (paged,), attn, rec)

            kw = {}
            sh = self.topo.paged_shardings(cfg, self.paged)
            if sh is not None:
                kw["out_shardings"] = sh
            donate = (0,) if self.donate else ()
            self._copy_fn = jax.jit(fn, donate_argnums=donate, **kw)
        return self._copy_fn

    # -- slot / block plumbing ---------------------------------------------
    def _mgr(self, b: int):
        """The BlockManager of the data shard owning batch slot ``b``."""
        return self.pool.manager(self.topo.shard_of_slot(b, self.B))

    def _table_offset(self, b: int) -> int:
        """Global pool id of slot ``b``'s shard-local block 0."""
        return self.topo.block_offset(self.topo.shard_of_slot(b, self.B),
                                      self.pool.blocks_per_shard)

    def _ensure_capacity(self, b: int, upto_pos: int):
        """Grow slot ``b``'s block table to cover positions [0, upto_pos)."""
        need = -(-upto_pos // self.block_size)
        assert need <= self.nb, (need, self.nb)
        mgr = self._mgr(b)
        while len(self.owned[b]) < need:
            blk = mgr.alloc(1)[0]
            self.tables[b, len(self.owned[b])] = blk
            self.owned[b].append(blk)
            self._tables_dev = None

    def _clear_row(self, b: int, release: bool = True):
        """Reset a released slot so its (inactive) lane reads no stale or
        garbage cache positions: n=1, cache_len=0 -> only its own window.
        ``release=False`` keeps the block accounting untouched (migration
        moves ownership instead of freeing it). ``seq_ids`` is zeroed with
        the rest of the row: a stale noise-stream id was harmless only
        because inactive lanes are no-ops, and the preemption/migration
        paths are judged against rows being *fully* clean."""
        if release:
            self._mgr(b).release_all(self.owned[b])
        self.owned[b] = []
        self.tables[b] = 0
        self.target[b] = 0
        self.reserved[b] = 0
        self.n_host[b] = 1
        self._tables_dev = None
        self._target_dev = None
        if self.poison[b]:
            self.poison[b] = 0
            self._poison_dev = None
        if self.plen[b]:
            self.plen[b] = 0
            self._plen_dev = None
        if self.seq_ids[b]:
            self.seq_ids[b] = 0
            self._seq_dev = None
        self.tokens = self.tokens.at[b].set(0)
        self.n = self.n.at[b].set(1)
        self.cand = self.cand.at[b].set(0)

    def _reset_recurrent_row(self, b: int):
        def rec(stacked, leaf):
            return leaf.at[:, b].set(0) if stacked else leaf.at[b].set(0)

        self.paged = TransformerLM._map_paged(
            self.cfg, (self.paged,), lambda stacked, leaf: leaf, rec)

    def _tables_device(self):
        if self._tables_dev is None:
            self._tables_dev = self.topo.put_batch(self.tables)
        return self._tables_dev

    def _target_device(self):
        if self._target_dev is None:
            self._target_dev = self.topo.put_batch(
                self.target.astype(np.int32))
        return self._target_dev

    def _poison_device(self):
        if self._poison_dev is None:
            self._poison_dev = self.topo.put_batch(self.poison)
        return self._poison_dev

    def _seq_device(self):
        if self._seq_dev is None:
            self._seq_dev = self.topo.put_batch(self.seq_ids)
        return self._seq_dev

    def _plen_device(self):
        if self._plen_dev is None:
            self._plen_dev = self.topo.put_batch(
                self.plen.astype(np.int32))
        return self._plen_dev

    def _set_poison(self, b: int, req: Request):
        """Refresh slot ``b``'s poison-mask entry for its new occupant."""
        v = int(self.faults is not None
                and req.seq_id in self.faults.poison_streams)
        if int(self.poison[b]) != v:
            self.poison[b] = v
            self._poison_dev = None

    # -- host cache tier plumbing (DESIGN.md §13) ----------------------------
    def _collect_block_payload(self, gids) -> list:
        """Attention pool rows for GLOBAL block ids ``gids``: ONE device
        pull, split host-side into a flat row list per block. Row order is
        the ``_map_paged`` leaf walk — ``_merge_block_rows`` replays the
        same walk, so the flat encoding round-trips without a schema."""
        if len(gids) == 0:
            return []
        g = jnp.asarray(np.asarray(gids, np.int32))
        flags, pulled = [], []

        def attn(stacked, leaf):
            flags.append(stacked)
            pulled.append(leaf[:, g] if stacked else leaf[g])
            return leaf

        TransformerLM._map_paged(self.cfg, (self.paged,), attn,
                                 lambda stacked, leaf: leaf)
        host = jax.device_get(pulled)
        return [[a[:, j] if st else a[j] for st, a in zip(flags, host)]
                for j in range(len(gids))]

    def _merge_block_rows(self, gid: int, rows):
        """Write one block's attention rows (``_map_paged`` walk order)
        into the pool at GLOBAL id ``gid`` — the same admission-path
        ``.at[].set`` merge the exact-resume upload uses; the round
        jaxpr/HLO never sees it."""
        it = iter(rows)

        def attn(stacked, leaf):
            a = next(it)
            if not isinstance(a, jax.Array):
                # explicit host copy: never let the device buffer alias an
                # arena slab that a later put may recycle
                a = jnp.asarray(np.array(a))
            return leaf.at[:, gid].set(a) if stacked else leaf.at[gid].set(a)

        self.paged = TransformerLM._map_paged(self.cfg, (self.paged,), attn,
                                              lambda stacked, leaf: leaf)

    def _collect_rec_row(self, b: int) -> list:
        """Slot ``b``'s recurrent state rows (leaf walk order), on host."""
        pulled = []

        def rec(stacked, leaf):
            pulled.append(leaf[:, b] if stacked else leaf[b])
            return leaf

        TransformerLM._map_paged(self.cfg, (self.paged,),
                                 lambda stacked, leaf: leaf, rec)
        return list(jax.device_get(pulled))

    def _restore_rec_row(self, b: int, rows):
        it = iter(rows)

        def rec(stacked, leaf):
            a = jnp.asarray(np.array(next(it)))
            return leaf.at[:, b].set(a) if stacked else leaf.at[b].set(a)

        self.paged = TransformerLM._map_paged(self.cfg, (self.paged,),
                                              lambda stacked, leaf: leaf, rec)

    def _make_spill_hook(self, shard: int):
        """BlockManager eviction -> host tier: when a registered cached-free
        block is reclaimed, copy its contents D2H into the arena under its
        chain key (skipping the pull when the key is already resident —
        chained keys are content-addressed). Returns None (drop outright)
        without a tier or attention leaves to spill."""
        if self.tier is None or not self.has_attn:
            return None
        off = self.topo.block_offset(shard, self.pool.blocks_per_shard)

        def hook(local_bid: int, key) -> bool:
            if self.tier.has_kv(shard, key):
                return True
            with self._span("serve.spill"):
                rows = self._collect_block_payload([local_bid + off])[0]
                return self.tier.put_kv(shard, key, rows)

        return hook

    def _stage_host_blocks(self, b: int, mgr, host_keys, pos0: int,
                           prefetched: Optional[dict] = None) -> int:
        """Re-admit host-resident KV blocks into slot ``b``'s table
        positions ``[pos0, pos0 + len(host_keys))`` through the async
        staging ring: upload ``k+1`` dispatches while ``k``'s merge is
        still executing (double-buffered, ``staging.depth`` in flight).
        The run is pinned first so the block allocations below — whose
        evictions spill INTO the same arena — cannot evict it mid-flight;
        a pin that fails truncates the run and prefill covers the rest.

        Partial failure (DESIGN.md §14): a staging run that dies mid-ring —
        an injected/real ``StagingFault``, an allocation failure, a corrupt
        entry read — must leave NOTHING behind: the ring is cleared so the
        next caller cannot ``take()`` uploads staged for this slot's table,
        and only blocks that completed the merge+register pair count as
        staged; everything short of that is rewritten by prefill (staging
        is a pure optimization, truncation is always safe). Returns the
        number of blocks staged."""
        shard = self.topo.shard_of_slot(b, self.B)
        pinned = []
        for key in host_keys:
            if prefetched is not None and key in prefetched:
                pinned.append(key)   # device-resident copy: no pin needed
                continue
            if not self.tier.pin_kv(shard, key):
                break
            pinned.append(key)
        try:
            self._ensure_capacity(
                b, (pos0 + len(pinned)) * self.block_size)
        except Exception:
            for key in pinned:
                if prefetched is None or key not in prefetched:
                    self.tier.unpin_kv(shard, key)
            raise
        try:
            staged = self._restage_host_blocks(
                shard, mgr, pinned,
                self.owned[b][pos0:pos0 + len(pinned)],
                prefetched=prefetched)
        finally:
            for key in pinned:
                if prefetched is None or key not in prefetched:
                    self.tier.unpin_kv(shard, key)
        return staged

    def _restage_host_blocks(self, shard: int, mgr, host_keys, block_ids,
                             prefetched: Optional[dict] = None) -> int:
        """The slot-less core of host-tier restaging (§13/§15): merge the
        tier entries under ``host_keys`` into the already-allocated
        shard-local ``block_ids`` (1:1, key order) through the async
        staging ring, registering each completed block. Callers own
        pinning and capacity. ``prefetched`` maps chain keys to device
        rows uploaded while the request was still queued (§15 prefetch):
        those merge directly — no pull, no H2D wait — and count
        ``prefetch_hits``; the ring is drained first so completed merges
        always form a key-order prefix (the contiguity every caller's
        coverage math depends on). Returns the number of blocks merged."""
        off = self.topo.block_offset(shard, self.pool.blocks_per_shard)
        ring = self.tier.staging
        staged = 0
        try:
            for j, key in enumerate(host_keys):
                if prefetched is not None and key in prefetched:
                    while True:          # keep commitment in key order
                        item = ring.take()
                        if item is None:
                            break
                        (blk, k2), devs = item
                        self._merge_block_rows(blk + off, devs)
                        mgr.register(blk, k2)
                        staged += 1
                    self._merge_block_rows(block_ids[j] + off,
                                           prefetched[key])
                    mgr.register(block_ids[j], key)
                    staged += 1
                    self.metrics.prefetch_hits += 1
                    continue
                rows = self.tier.get_kv(shard, key)   # counts the host hit
                if rows is None:     # corrupt/tripped mid-run: truncate
                    break
                ring.stage((block_ids[j], key), rows)
                if len(ring) >= ring.depth:           # drain behind the ring
                    (blk, k2), devs = ring.take()
                    self._merge_block_rows(blk + off, devs)
                    mgr.register(blk, k2)
                    staged += 1
            while True:
                item = ring.take()
                if item is None:
                    break
                (blk, k2), devs = item
                self._merge_block_rows(blk + off, devs)
                mgr.register(blk, k2)
                staged += 1
        except Exception:
            # drop every in-flight upload (staged-but-unmerged blocks are
            # rewritten by prefill — `staged` only counts completed merges)
            ring.clear()
            self.metrics.staging_errors += 1
            self.tier.record_failure()
        self.metrics.host_staged_blocks += staged
        return staged

    # -- sequence migration / priority preemption (DESIGN.md §12) -----------
    def _live_blocks(self, b: int) -> int:
        """Leading owned blocks whose contents the next round still reads:
        those holding positions [0, n-1). The verify window re-encodes
        position n-1 onward every round (slot 0 carries the last accepted
        token), so later blocks are garbage-by-design and need no spill."""
        return -(-max(int(self.n_host[b]) - 1, 0) // self.block_size)

    def _park_payload(self, b: int, nb_live: int) -> dict:
        """Device->host pull of everything slot ``b``'s exact resume needs
        from the cache: the ``nb_live`` pool block rows (attention leaves,
        in table order) and the per-slot recurrent state row."""
        gids = jnp.asarray(self.tables[b, :nb_live].astype(np.int32)
                           + self._table_offset(b))

        def attn(stacked, leaf):
            return leaf[:, gids] if stacked else leaf[gids]

        def rec(stacked, leaf):
            return leaf[:, b] if stacked else leaf[b]

        return jax.device_get(TransformerLM._map_paged(
            self.cfg, (self.paged,), attn, rec))

    def preempt_slot(self, b: int) -> Request:
        """Evict the running slot ``b``: spill its live block contents (and
        recurrent state) to a host-side parking entry, release its blocks
        and slot, and requeue the request (original submit time + arrival
        order) for exact resume. Tokens of the resumed run are bitwise
        those of an uninterrupted one: the parked n/cand snapshot restores
        the verify window exactly and noise streams are position-keyed."""
        req = self.slots[b]
        assert req is not None, f"slot {b} is not occupied"
        nb_live = self._live_blocks(b)
        if self.tier is None:
            self.parked[req.uid] = ParkedSequence(
                n=int(self.n_host[b]),
                tokens=np.asarray(self.tokens[b]),
                cand=np.asarray(self.cand[b]),
                nb_live=nb_live,
                payload=self._park_payload(b, nb_live))
        else:
            self.parked[req.uid] = self._park_tiered(req, b, nb_live)
        self._mgr(b).spill(self.owned[b])
        self.owned[b] = []
        self.slots[b] = None
        self._clear_row(b, release=False)
        self.queue.requeue(req)
        self._journal("park", uid=int(req.uid))
        req.preemptions += 1
        self.metrics.preemptions += 1
        self.metrics.blocks_parked += nb_live
        return req

    def _park_tiered(self, req: Request, b: int, nb_live: int) -> ParkedSequence:
        """Park into the host tier (DESIGN.md §13): the victim's full
        prompt blocks go to the shared ``kv`` namespace — refcount-pinned,
        stored ONCE however many victims share the prefix — and only the
        private remainder (tail block rows + the recurrent state row) is
        parked per victim: in the arena when it fits, raw host memory as
        the overflow fallback (parking must never fail)."""
        shard = self.topo.shard_of_slot(b, self.B)
        off = self._table_offset(b)
        prompt = np.asarray(req.prompt)
        nb_pub = (min((len(prompt) - 1) // self.block_size, nb_live)
                  if self._kv_share else 0)
        keys = chain_hashes(prompt, self.block_size, nb_pub)
        # pull only the blocks whose keys are not already arena-resident
        # (content-addressed: a resident entry IS this block's contents)
        need = [jb for jb in range(nb_pub)
                if not self.tier.has_kv(shard, keys[jb])]
        payloads = dict(zip(need, self._collect_block_payload(
            [int(self.tables[b, jb]) + off for jb in need])))
        kv_keys = []
        for jb in range(nb_pub):
            ok = (self.tier.put_kv(shard, keys[jb], payloads[jb], pin=True)
                  if jb in payloads else self.tier.pin_kv(shard, keys[jb]))
            if not ok:          # arena full / entry evicted: rest goes private
                break
            kv_keys.append(keys[jb])
        tail = self._collect_block_payload(
            [int(self.tables[b, jb]) + off
             for jb in range(len(kv_keys), nb_live)]) if self.has_attn \
            else [[] for _ in range(len(kv_keys), nb_live)]
        rec = self._collect_rec_row(b) if _has_recurrent(self.cfg) else []
        private = list(rec)
        for rows in tail:
            private.extend(rows)
        in_arena = self.tier.put_park(req.uid, private)
        return ParkedSequence(
            n=int(self.n_host[b]), tokens=np.asarray(self.tokens[b]),
            cand=np.asarray(self.cand[b]), nb_live=nb_live,
            kv_keys=tuple(kv_keys), n_rec=len(rec),
            rows_per_block=len(tail[0]) if tail else 0,
            in_arena=in_arena, private=None if in_arena else private,
            shard=shard)

    def _resume(self, req: Request, b: int, parked: ParkedSequence):
        """Re-admit a parked request into slot ``b`` exactly where it left
        off: re-hit still-valid prefix blocks, upload the parked contents of
        the rest (host tier or legacy payload), restore the per-slot
        n/cand/tokens snapshot."""
        req.admit_time = time.monotonic()
        if parked.cold:
            # checkpoint-restored park (§16): no payload or pins exist in
            # this process — rebuild through the disk-tier fall-through +
            # re-prefill (bitwise-exact either way)
            self.metrics.resume_recomputes += 1
            return self._resume_cold(req, b, parked)
        if parked.payload is None:
            return self._resume_tiered(req, b, parked)
        prompt = np.asarray(req.prompt, np.int64)
        L_p = len(prompt)
        mgr = self._mgr(b)
        nb_live = parked.nb_live
        # full prompt blocks may have survived the spill in this shard's
        # prefix cache (spill leaves hashed blocks cached-free) — re-hit
        # them instead of re-uploading
        hits, keys = [], []
        nb_full = min((L_p - 1) // self.block_size, nb_live)
        if self.prefix_enabled and nb_full:
            hits, keys = mgr.lookup_prefix(prompt, nb_full)
        req.prefix_hit_blocks += len(hits)
        # hits are owned the moment lookup returns: record them BEFORE the
        # (fault-injectable) alloc so an unwind releases them (§14)
        self.owned[b] = list(hits)
        self.tables[b] = 0
        self.tables[b, :len(hits)] = hits
        self._tables_dev = None
        fresh = mgr.alloc(nb_live - len(hits))
        owned = list(hits) + fresh
        self.owned[b] = list(owned)
        self.tables[b, :nb_live] = owned

        # upload the parked payload: non-hit block rows + the recurrent row
        fresh_pos = np.arange(len(hits), nb_live)
        gids = jnp.asarray(np.asarray(fresh, np.int64).astype(np.int32)
                           + self._table_offset(b))

        def attn(stacked, pleaf, kleaf):
            if len(fresh_pos) == 0:
                return pleaf
            if stacked:
                return pleaf.at[:, gids].set(jnp.asarray(kleaf[:, fresh_pos]))
            return pleaf.at[gids].set(jnp.asarray(kleaf[fresh_pos]))

        def rec(stacked, pleaf, kleaf):
            if stacked:
                return pleaf.at[:, b].set(jnp.asarray(kleaf))
            return pleaf.at[b].set(jnp.asarray(kleaf))

        self.paged = TransformerLM._map_paged(
            self.cfg, (self.paged, parked.payload), attn, rec)

        # per-slot state: the exact park-time snapshot
        self.tokens = self.tokens.at[b].set(
            jnp.asarray(parked.tokens, jnp.int32))
        self.n = self.n.at[b].set(parked.n)
        self.cand = self.cand.at[b].set(jnp.asarray(parked.cand, jnp.int32))
        self.seq_ids[b] = req.seq_id
        self._seq_dev = None
        self.n_host[b] = parked.n

        # re-publish the freshly uploaded full prompt blocks
        if self.prefix_enabled:
            for j in range(len(hits), nb_full):
                mgr.register(owned[j], keys[j])

        self.slots[b] = req
        self._set_poison(b, req)
        self.target[b] = L_p + req.new_tokens
        self._target_dev = None
        if self.plen[b] != L_p:
            self.plen[b] = L_p
            self._plen_dev = None
        self.reserved[b] = self._worst_case_blocks(req)
        self.metrics.resumes += 1

    def _resume_tiered(self, req: Request, b: int, parked: ParkedSequence):
        """Exact resume from a tier-split park: device re-hits first (spill
        left hashed blocks cached-free), then the pinned shared ``kv``
        entries, then the private tail rows; the recurrent row is restored
        bit-exactly from the private part, so device KV hits need no
        snapshot gating here (unlike a fresh admission).

        The whole parked payload is prefetched BEFORE any engine state is
        touched (§14): a piece gone missing — a checksum failure demoted
        the entry to a miss, the breaker tripped, the arena evicted under
        pressure — then routes to :meth:`_resume_cold` (recompute) with
        nothing to unwind. Prefetched shared rows stay valid until the park
        pins drop at the end; the merge copies them out."""
        prompt = np.asarray(req.prompt, np.int64)
        L_p = len(prompt)
        mgr = self._mgr(b)
        # the pinned kv entries live under the PARKING shard's tier
        # partition — resume may land elsewhere (mesh routing), and the
        # entries are content-addressed, so read them where they are
        shard = parked.shard
        off = self._table_offset(b)
        nb_live = parked.nb_live
        n_shared = len(parked.kv_keys)

        private = (self.tier.take_park(req.uid) if parked.in_arena
                   else (parked.private or []))
        shared, missing = [], parked.in_arena and private is None
        if not missing:
            for key in parked.kv_keys:
                rows = self.tier.get_kv(shard, key)
                if rows is None:      # pinned entry corrupt / tier tripped
                    missing = True
                    break
                shared.append(rows)
        if missing:
            self._discard_park(req.uid, parked)
            self.metrics.resume_recomputes += 1
            return self._resume_cold(req, b, parked)
        # private payload: recurrent row arrays first, then the rows of
        # tail blocks [n_shared, nb_live) (flat, rows_per_block each)
        rec_rows = private[:parked.n_rec]
        tail = private[parked.n_rec:]
        rpb = parked.rows_per_block

        hits, keys = [], []
        nb_full = min((L_p - 1) // self.block_size, nb_live)
        if self._kv_share and nb_full:
            hits, keys = mgr.lookup_prefix(prompt, nb_full)
        self.owned[b] = list(hits)
        self.tables[b] = 0
        self.tables[b, :len(hits)] = hits
        self._tables_dev = None
        fresh = mgr.alloc(nb_live - len(hits))
        owned = list(hits) + fresh
        self.owned[b] = list(owned)
        self.tables[b, :nb_live] = owned

        host_restored = 0
        for jb in range(len(hits), nb_live):
            if jb < n_shared:
                rows = shared[jb]
                host_restored += 1
            else:
                t0 = (jb - n_shared) * rpb
                rows = tail[t0:t0 + rpb]
            self._merge_block_rows(owned[jb] + off, rows)
        req.prefix_hit_blocks += len(hits) + host_restored
        if _has_recurrent(self.cfg):
            self._restore_rec_row(b, rec_rows)

        # per-slot state: the exact park-time snapshot
        self.tokens = self.tokens.at[b].set(
            jnp.asarray(parked.tokens, jnp.int32))
        self.n = self.n.at[b].set(parked.n)
        self.cand = self.cand.at[b].set(jnp.asarray(parked.cand, jnp.int32))
        self.seq_ids[b] = req.seq_id
        self._seq_dev = None
        self.n_host[b] = parked.n

        # re-publish the rebuilt full prompt blocks, drop the park pins
        if self._kv_share:
            for jb in range(len(hits), nb_full):
                mgr.register(owned[jb], keys[jb])
        for key in parked.kv_keys:
            self.tier.unpin_kv(shard, key)

        self.slots[b] = req
        self._set_poison(b, req)
        self.target[b] = L_p + req.new_tokens
        self._target_dev = None
        if self.plen[b] != L_p:
            self.plen[b] = L_p
            self._plen_dev = None
        self.reserved[b] = self._worst_case_blocks(req)
        self.metrics.resumes += 1

    def _resume_cold(self, req: Request, b: int, parked: ParkedSequence):
        """Rebuild a parked slot by recompute when its payload is gone
        (corruption demoted to a miss, tripped tier, arena eviction):
        re-prefill positions ``[0, n-1)`` from the parked accepted-token
        row, then restore the ``n``/``cand``/``tokens`` snapshot. K/V (and
        recurrent state) at a position are pure functions of the preceding
        tokens and chunk decomposition is bitwise-invariant — the standing
        exactness invariant every prefill path rests on — so a cold resume
        emits tokens bitwise identical to a warm one; it just pays prefill
        compute (``resume_recomputes`` counts these)."""
        prompt = np.asarray(req.prompt, np.int64)
        L_p = len(prompt)
        mgr = self._mgr(b)
        n = parked.n
        nb_live = parked.nb_live
        toks = np.asarray(parked.tokens, np.int64)
        # recurrent archs would need the state snapshot at any reuse
        # boundary — gone with the payload — so they rebuild from zero;
        # attention archs re-hit device-cached prompt blocks AND fall
        # through to the host/disk tiers (§16: after a restart the device
        # cache is empty but the chain keys still resolve on disk — this
        # is exactly where a warm restart earns its fewer prefill chunks)
        hits, keys, host_keys = [], [], []
        nb_full = min((L_p - 1) // self.block_size, nb_live)
        if self._kv_share and nb_full and not _has_recurrent(self.cfg):
            if self.tier is not None:
                hits, keys, host_keys = mgr.lookup_prefix_tiered(
                    prompt, nb_full, tier=self.tier,
                    shard=self.topo.shard_of_slot(b, self.B))
            else:
                hits, keys = mgr.lookup_prefix(prompt, nb_full)
        self.owned[b] = list(hits)
        self.tables[b] = 0
        self.tables[b, :len(hits)] = hits
        self._tables_dev = None
        staged = (self._stage_host_blocks(b, mgr, host_keys, len(hits))
                  if host_keys else 0)
        req.prefix_hit_blocks += len(hits) + staged
        self._ensure_capacity(b, nb_live * self.block_size)
        if _has_recurrent(self.cfg):
            self._reset_recurrent_row(b)

        start = (len(hits) + staged) * self.block_size
        table_row = jnp.asarray(self.tables[b:b + 1] + self._table_offset(b))
        row = jnp.asarray([b], jnp.int32)
        for C in prefill_chunks(n - 1 - start, self.prefill_chunk):
            chunk = jnp.asarray(toks[None, start:start + C], jnp.int32)
            pf = self._prefill_fn(C)
            pf_args = (self.params, self.paged, table_row, row, chunk,
                       jnp.asarray([start], jnp.int32))
            self._contract_check("prefill", pf, pf_args)
            self.paged = pf(*pf_args)
            start += C
            req.prefill_calls += 1
            self.metrics.prefill_calls += 1
        if self._kv_share and not _has_recurrent(self.cfg):
            for j in range(len(hits) + staged, nb_full):
                mgr.register(self.owned[b][j], keys[j])

        # per-slot state: the exact park-time snapshot
        self.tokens = self.tokens.at[b].set(
            jnp.asarray(parked.tokens, jnp.int32))
        self.n = self.n.at[b].set(n)
        self.cand = self.cand.at[b].set(jnp.asarray(parked.cand, jnp.int32))
        self.seq_ids[b] = req.seq_id
        self._seq_dev = None
        self.n_host[b] = n

        self.slots[b] = req
        self._set_poison(b, req)
        self.target[b] = L_p + req.new_tokens
        self._target_dev = None
        if self.plen[b] != L_p:
            self.plen[b] = L_p
            self._plen_dev = None
        self.reserved[b] = self._worst_case_blocks(req)
        self.metrics.resumes += 1

    def _discard_park(self, uid: int, parked: ParkedSequence):
        """Release a parked payload's tier resources without resuming it
        (cancel, failed resume): the park entry and the shared-kv pins.
        Tolerant of partial consumption — ``drop``/``unpin`` are no-ops on
        already-consumed entries."""
        if self.tier is None or parked.cold:
            # a cold (checkpoint-restored) park holds no pins in THIS
            # process — unpinning its keys could steal a pin a live park
            # of the same prefix legitimately owns (§16)
            return
        if parked.in_arena:
            self.tier.drop_park(uid)
        for key in parked.kv_keys:
            self.tier.unpin_kv(parked.shard, key)

    def migrate_slot(self, b_src: int, b_dst: int):
        """Move a live sequence to a free slot: across shard sub-pools
        under a mesh (device block copy into freshly allocated landing
        blocks + one table-row re-upload + per-slot state move) or within
        one (the blocks stay put; only the table row and state move).
        Bit-exact by construction — tokens and noise streams are
        placement-independent, and the block contents are copied bitwise.
        Callers are responsible for capacity: a cross-shard move needs
        ``len(owned)`` free blocks on the destination shard (and should
        leave its outstanding reservations coverable — ``_try_rebalance``
        checks ``reserved`` before moving)."""
        req = self.slots[b_src]
        assert req is not None, f"slot {b_src} is not occupied"
        assert self.slots[b_dst] is None, f"slot {b_dst} is occupied"
        s = self.topo.shard_of_slot(b_src, self.B)
        t = self.topo.shard_of_slot(b_dst, self.B)
        n_owned = len(self.owned[b_src])
        src_ids = np.zeros(self.nb, np.int32)   # sink-padded: id 0 -> id 0
        dst_ids = np.zeros(self.nb, np.int32)
        if s == t:
            new_owned = list(self.owned[b_src])   # blocks stay put
        else:
            new_owned = self.pool.begin_migration(s, t, n_owned)
            src_ids[:n_owned] = (self.tables[b_src, :n_owned]
                                 + self._table_offset(b_src))
            dst_ids[:n_owned] = (np.asarray(new_owned, np.int32)
                                 + self._table_offset(b_dst))
            self.metrics.blocks_migrated += n_owned
        copy_fn = self._copy_blocks_fn()
        copy_args = (self.paged, jnp.asarray(src_ids), jnp.asarray(dst_ids),
                     jnp.asarray(b_src, jnp.int32),
                     jnp.asarray(b_dst, jnp.int32))
        self._contract_check("migration_copy", copy_fn, copy_args)
        self.paged = copy_fn(*copy_args)
        if s != t:
            self.pool.finish_migration(s, self.owned[b_src])
            if self._kv_share:
                # re-publish the copied full prompt blocks under the
                # destination shard's cache (content-identical; first
                # writer wins)
                prompt = np.asarray(req.prompt)
                nb_full = min((len(prompt) - 1) // self.block_size, n_owned)
                keys = chain_hashes(prompt, self.block_size, nb_full)
                for j in range(nb_full):
                    self.pool.manager(t).register(new_owned[j], keys[j])

        # per-slot device rows ride along (the recurrent state row moved
        # inside the copy step)
        for name in ("tokens", "cand"):
            arr = getattr(self, name)
            setattr(self, name, arr.at[b_dst].set(arr[b_src]))
        self.n = self.n.at[b_dst].set(self.n[b_src])
        if self.seq_ids[b_dst] != self.seq_ids[b_src]:
            self.seq_ids[b_dst] = self.seq_ids[b_src]
            self._seq_dev = None
        if self.plen[b_dst] != self.plen[b_src]:
            self.plen[b_dst] = self.plen[b_src]
            self._plen_dev = None

        # host-side bookkeeping moves, then the source row is cleared
        # WITHOUT releasing (ownership moved, it was not freed)
        self.tables[b_dst] = 0
        self.tables[b_dst, :n_owned] = new_owned
        self.owned[b_dst] = list(new_owned)
        self.slots[b_dst] = req
        self.target[b_dst] = self.target[b_src]
        self.reserved[b_dst] = self.reserved[b_src]
        self.n_host[b_dst] = self.n_host[b_src]
        if self.poison[b_dst] != self.poison[b_src]:
            self.poison[b_dst] = self.poison[b_src]
            self._poison_dev = None
        self.slots[b_src] = None
        self.owned[b_src] = []
        self._clear_row(b_src, release=False)
        req.migrations += 1
        self.metrics.migrations += 1

    # -- staging area / in-loop adoption (DESIGN.md §15) ---------------------
    def _staged_total(self) -> int:
        return sum(len(entries) for entries in self.staged)

    def _unstage_all(self):
        """Return every staged entry to the queue (``requeue`` preserves
        the original arrival rank) and its worst-case blocks to the pool
        (registered restaged blocks drop to cached-free — still hittable)."""
        for s in range(self.topo.data_size):
            mgr = self.pool.manager(s)
            for e in self.staged[s]:
                mgr.release_all(e.blocks)
                self.ledger.release(s, e.req.uid)
                self.queue.requeue(e.req)
            self.staged[s] = []

    def _reconcile_staging(self):
        """Re-assert the staging invariants at every sync boundary: staged
        entries exist ONLY while every slot is occupied (a free slot hands
        the backlog back to full lookahead/preempt/rebalance admission,
        which the device adoption scan cannot replicate), and the area
        never outranks the queue head (a higher-priority arrival unstages
        it instead of waiting behind committed descriptors)."""
        if self._staged_total() == 0:
            return
        if any(s is None for s in self.slots):
            self._unstage_all()
            return
        head = self.queue.peek()
        if head is not None:
            hk = (head.priority, head.deadline_time, head._seq)
            if any(hk < e.key for entries in self.staged for e in entries):
                self._unstage_all()

    def _build_staged(self, req: Request, shard: int,
                      need: int) -> StagedEntry:
        """Build one staged entry on ``shard``: worst-case blocks up front
        (an adopted row never allocates mid-loop — the same run-to-
        completion guarantee admission reserves), with device prefix hits
        and host-tier restaged blocks covering the leading prompt
        positions. Recurrent stacks stage from scratch: their un-paged
        state row is zeroed at adoption, so a KV prefix without its
        boundary snapshot would desynchronize. Freshly allocated blocks
        are NOT registered in the prefix cache — their contents only
        become valid as the in-loop forced prefill writes them."""
        mgr = self.pool.manager(shard)
        prompt = np.asarray(req.prompt, np.int64)
        L_p = len(prompt)
        hits, keys, host_keys = [], [], []
        nb_full = (L_p - 1) // self.block_size
        if self._kv_share and nb_full and not _has_recurrent(self.cfg):
            hits, keys, host_keys = mgr.lookup_prefix_tiered(
                prompt, nb_full, tier=self.tier, shard=shard)
        try:
            fresh = mgr.alloc(need - len(hits))
        except Exception:
            mgr.release_all(hits)
            raise
        blocks = list(hits) + fresh
        try:
            staged_host = 0
            if host_keys and self.tier is not None:
                pre = self._take_prefetched(req.uid, shard)
                pinned = []
                for key in host_keys:
                    if pre is not None and key in pre:
                        pinned.append(key)    # device copy: no pin needed
                        continue
                    if not self.tier.pin_kv(shard, key):
                        break
                    pinned.append(key)
                try:
                    staged_host = self._restage_host_blocks(
                        shard, mgr, pinned,
                        blocks[len(hits):len(hits) + len(pinned)],
                        prefetched=pre)
                finally:
                    for key in pinned:
                        if pre is None or key not in pre:
                            self.tier.unpin_kv(shard, key)
        except Exception:
            mgr.release_all(blocks)
            raise
        cov = len(hits) + staged_host
        req.prefix_hit_blocks = cov
        table_row = np.zeros(self.nb, np.int32)
        table_row[:len(blocks)] = blocks
        poison = int(self.faults is not None
                     and req.seq_id in self.faults.poison_streams)
        return StagedEntry(
            req=req, shard=shard, prompt=prompt.astype(np.int32),
            n0=cov * self.block_size + 1, plen=L_p,
            target=L_p + req.new_tokens, blocks=blocks,
            table_row=table_row, poison=poison,
            key=(req.priority, req.deadline_time, req._seq))

    def _stage_pending(self):
        """Fill the staging area from the queue, strictly in queue order
        (§15): runs after host admission, only while every slot is
        occupied. Stops at the first request that cannot stage — skipping
        it would let a later request adopt first and invert the committed
        order. Block claims go through the ``StagingLedger``, so staging
        only ever consumes headroom net of resident reservations."""
        if self.staging_slots == 0 or not self.queue:
            return
        if any(s is None for s in self.slots):
            return
        D = self.topo.data_size
        capacity = sum(self.staging_slots - len(self.staged[s])
                       for s in range(D))
        if capacity <= 0:
            return
        for req in self.queue.lookahead(capacity):
            if req.uid in self.parked:
                break       # parked resumes need the host admission path
            need = self._worst_case_blocks(req)
            best = None
            for s in range(D):
                if len(self.staged[s]) >= self.staging_slots:
                    continue
                h = self._headroom(s)
                if h >= need and (best is None or h > best[1]):
                    best = (s, h)
            if best is None:
                break
            s, h = best
            if not self.ledger.try_claim(s, req.uid, need, h):
                break
            try:
                entry = self._build_staged(req, s, need)
            except Exception:
                # staging is a pure optimization: leave the request queued
                # (host admission will retry it) and stop the pass
                self.ledger.release(s, req.uid)
                break
            self.queue.remove(req)
            self._drop_prefetched(req.uid)
            self.staged[s].append(entry)
            self.metrics.staged_sequences += 1

    def _take_prefetched(self, uid: int, shard: int) -> Optional[dict]:
        """Claim ``uid``'s prefetched device rows for an admission or
        staging on ``shard`` — None when nothing was prefetched or the
        copies live under another shard's key partition."""
        ent = self._prefetched.pop(uid, None)
        if ent is None:
            return None
        p_shard, rows = ent
        return rows if p_shard == shard else None

    def _drop_prefetched(self, uid: int):
        self._prefetched.pop(uid, None)

    def _prefetch_queued(self):
        """Proactive host-tier prefetch (§15 satellite): while a request
        waits in the queue, push its host-resident prefix blocks through
        the async staging ring ahead of time; admission/staging later
        merges the device-resident copies (``prefetch_hits``) instead of
        paying the pull + H2D wait inline. Copies are content-addressed
        and immutable, so no pins are held; entries for requests that left
        the queue are dropped here."""
        if (not self.host_prefetch or self.tier is None
                or not self.kv_prefix or self.prefetch_budget == 0):
            return
        queued = {r.uid for r in self.queue.requests()}
        for uid in list(self._prefetched):
            if uid not in queued:
                self._drop_prefetched(uid)
        budget = self.prefetch_budget
        for req in self.queue.lookahead(max(self.lookahead, 1)):
            if budget <= 0:
                break
            if req.uid in self._prefetched or req.uid in self.parked:
                continue
            prompt = np.asarray(req.prompt, np.int64)
            nb_full = (len(prompt) - 1) // self.block_size
            if nb_full <= 0:
                continue
            keys = chain_hashes(prompt, self.block_size, nb_full)
            # route guess: the max-headroom shard an admission would pick;
            # a different landing shard just wastes the copies
            shard = max(range(self.topo.data_size), key=self._headroom)
            ring = self.tier.staging
            rows_by_key = {}
            try:
                for key in keys:
                    if budget <= 0:
                        break
                    if not self.tier.has_kv(shard, key):
                        break           # contiguous leading run only
                    rows = self.tier.get_kv(shard, key)
                    if rows is None:
                        break
                    # private host copies: prefetch holds no pins, and the
                    # ring's device_put is async — a slab view could be
                    # evicted and rewritten under an in-flight upload
                    ring.stage((key,), [np.array(a) for a in rows])
                    budget -= 1
                    if len(ring) >= ring.depth:
                        (k2,), devs = ring.take()
                        rows_by_key[k2] = devs
                while True:
                    item = ring.take()
                    if item is None:
                        break
                    (k2,), devs = item
                    rows_by_key[k2] = devs
            except Exception:
                ring.clear()
                self.metrics.staging_errors += 1
                self.tier.record_failure()
            if rows_by_key:
                self._prefetched[req.uid] = (shard, rows_by_key)

    # -- admission -----------------------------------------------------------
    def _worst_case_blocks(self, req: Request) -> int:
        # every prompt+generation block a fresh allocation, window at W_max
        return -(-(len(req.prompt) + req.new_tokens + self.W_max)
                 // self.block_size)

    def _outstanding_reservations(self, shard: int) -> int:
        """Blocks already promised to the shard's in-flight slots but not
        yet allocated (their tables grow lazily as n advances)."""
        return int(sum(max(0, int(self.reserved[b]) - len(self.owned[b]))
                       for b in self.topo.slot_range(shard, self.B)
                       if self.slots[b] is not None))

    def _free_slot_in(self, shard: int) -> Optional[int]:
        for b in self.topo.slot_range(shard, self.B):
            if self.slots[b] is None:
                return b
        return None

    def _headroom(self, shard: int) -> int:
        return (self.pool.available(shard)
                - self._outstanding_reservations(shard))

    def _route(self, req: Request) -> Optional[int]:
        """Pool-pressure admission routing: the free slot on the shard with
        the most block headroom that still covers the request's worst case
        (single shard: the lowest free slot, iff the pool fits it)."""
        headroom = {}
        for s in range(self.topo.data_size):
            if self._free_slot_in(s) is not None:
                headroom[s] = self._headroom(s)
        shard = self.pool.route(self._worst_case_blocks(req), headroom)
        return None if shard is None else self._free_slot_in(shard)

    def _try_rebalance(self, req: Request) -> Optional[int]:
        """Shard rebalancing: when no single shard has a free slot AND
        enough headroom for ``req``, look for a resident whose migration to
        another shard both fits there (its full remaining reservation) and
        frees enough capacity — slot and blocks — on its home shard to
        admit ``req``. Cheapest sufficient move (fewest copied blocks)
        wins. Returns the admission slot, or None."""
        if not self.rebalance or self.topo.data_size == 1:
            return None
        need = self._worst_case_blocks(req)
        best = None
        for v in range(self.B):
            if self.slots[v] is None:
                continue
            s_v = self.topo.shard_of_slot(v, self.B)
            # once v leaves, its slot frees and its blocks + outstanding
            # reservation return to s_v's headroom
            if self._headroom(s_v) + int(self.reserved[v]) < need:
                continue
            for t in range(self.topo.data_size):
                if t == s_v:
                    continue
                b_dst = self._free_slot_in(t)
                if b_dst is None or self._headroom(t) < int(self.reserved[v]):
                    continue
                cand = (len(self.owned[v]), v, b_dst)
                if best is None or cand < best:
                    best = cand
        if best is None:
            return None
        _, v, b_dst = best
        try:
            self.migrate_slot(v, b_dst)
        except MemoryError:
            # injected landing-block allocation failure (§14): nothing was
            # mutated before begin_migration's alloc, so just don't move
            return None
        return self._route(req)

    def _evictable(self, head: Request) -> list[int]:
        """Running slots the queue head may preempt: strictly lower
        priority AND below the progress floor (slots past
        ``preempt_floor`` of their generation target are protected — they
        free their slot soon anyway). Lowest priority first, then cheapest
        park."""
        out = []
        for b in range(self.B):
            r = self.slots[b]
            if r is None or r.priority <= head.priority:
                continue
            prog = (int(self.n_host[b]) - len(r.prompt)) / max(
                1, r.new_tokens)
            if prog >= self.preempt_floor:
                continue
            out.append(b)
        out.sort(key=lambda b: (-self.slots[b].priority,
                                self._live_blocks(b)))
        return out

    def _try_preempt(self, head: Request) -> Optional[int]:
        """Priority preemption: evict, on a single shard, the smallest
        prefix of evictable (lowest-priority, below-floor) slots whose
        freed reservations plus current headroom cover the head's worst
        case; park each victim for exact resume; route the head."""
        if not self.preempt:
            return None
        need = self._worst_case_blocks(head)
        by_shard: dict[int, list[int]] = {}
        for b in self._evictable(head):
            by_shard.setdefault(
                self.topo.shard_of_slot(b, self.B), []).append(b)
        best = None
        for s, vs in by_shard.items():
            gain = self._headroom(s)
            took = []
            for b in vs:
                gain += int(self.reserved[b])
                took.append(b)
                if gain >= need:
                    break
            if gain >= need and (best is None or len(took) < len(best)):
                best = took
        if best is None:
            return None
        for b in best:
            self.preempt_slot(b)
        return self._route(head)

    def _poll_queue_deadlines(self):
        """Count SLO expiries of requests still queued or parked — without
        this, a request that blows its deadline before ever running (or
        while parked by preemption) is invisible until it happens to
        finish (the ``deadline_miss_count`` undercount bug)."""
        now = time.monotonic()
        for req in self.queue.requests():
            if (req.deadline is not None and not req.queue_deadline_missed
                    and now > req.deadline_time):
                req.queue_deadline_missed = True
                self.metrics.deadline_missed_in_queue += 1

    def _admit_pending(self):
        """Lookahead admission (DESIGN.md §12): scan up to ``lookahead``
        queued requests in queue order and admit the first routable one —
        a small fitting request behind an oversized head no longer
        head-of-line blocks. The head may additionally claim capacity by
        shard rebalancing (any candidate may) or priority preemption (head
        only — preempting for a lower-ranked request would invert the
        queue order). Every admission that jumps the head ages it
        (``Request.bypassed``); at ``max_head_bypass`` the scan narrows to
        the head alone, so the head admits next and cannot starve."""
        while self.queue:
            cands = self.queue.lookahead(self.lookahead)
            head = cands[0]
            if head.bypassed >= self.max_head_bypass:
                cands = [head]            # aging bound reached: head-only
            admitted = None
            faulted = False
            for req in cands:
                b = self._route(req)
                if b is None:
                    b = self._try_rebalance(req)
                if b is None and req is head:
                    b = self._try_preempt(head)
                if b is not None:
                    self.queue.remove(req)
                    try:
                        with self._span("serve.admit_request",
                                        uid=int(req.uid)):
                            self._admit(req, b)
                    except Exception as e:
                        # quarantine the failure to THIS request (§14):
                        # unwind the half-built slot (releasing whatever
                        # blocks it had claimed), then retry or fail it —
                        # the other slots and the queue are untouched, so
                        # rescan the lookahead and keep admitting (a fault
                        # here must not head-of-line block the pass; the
                        # retry budget bounds re-admission attempts)
                        self.slots[b] = None
                        self._clear_row(b)
                        self._fail_request(
                            req, "admission", f"{type(e).__name__}: {e}",
                            retryable=True)
                        faulted = True
                    admitted = req
                    break
            if admitted is None:
                break
            if faulted:
                continue
            if admitted is not head:
                head.bypassed += 1
                self.metrics.head_bypass_admissions += 1

    def _admit(self, req: Request, b: int):
        parked = self.parked.pop(req.uid, None)
        if parked is not None:            # preempted: exact resume path
            try:
                self._resume(req, b, parked)
            except Exception:
                # the park is consumed/unreliable after a failed resume:
                # release its tier resources; a retry re-admits from the
                # prompt (a full restart on the same stream is bit-exact)
                self._discard_park(req.uid, parked)
                raise
            self._journal("admit", uid=int(req.uid))
            kill_point("post_admit")
            return
        req.admit_time = time.monotonic()
        prompt = np.asarray(req.prompt, np.int64)
        L_p = len(prompt)
        mgr = self._mgr(b)
        shard = self.topo.shard_of_slot(b, self.B)

        # prefix-cache: reuse full blocks strictly below position L_p - 1
        # (the verify window rewrites position n-1 = L_p-1 onward, so those
        # blocks stay read-only and shareable). Per-shard cache: hits can
        # only come from the sub-pool this slot decodes through; device
        # misses fall through to the host tier (DESIGN.md §13).
        hits, keys, host_keys = [], [], []
        nb_full = (L_p - 1) // self.block_size
        with self._span("serve.prefix_lookup", uid=int(req.uid)):
            if self._kv_share and nb_full:
                hits, keys, host_keys = mgr.lookup_prefix_tiered(
                    prompt, nb_full, tier=self.tier, shard=shard)
            elif self.rec_prefix and nb_full:
                keys = chain_hashes(prompt, self.block_size, nb_full)

        rec_rows, rec_bound = None, 0
        if self.rec_prefix and nb_full:
            # a prefix hit for a recurrent stack needs BOTH halves at one
            # block boundary j: KV blocks [0, j) coverable (device hits +
            # the contiguous host run; trivially all of them when the arch
            # has no attention layers) AND the recurrent-state snapshot at
            # keys[j-1] host-resident. Pick the largest such j.
            cover = (len(hits) + len(host_keys)) if self.has_attn else nb_full
            for jj in range(cover, 0, -1):
                rows = self.tier.get_rec(shard, keys[jj - 1])
                if rows is not None:
                    # copied out now: block allocs below spill into the
                    # same arena and could recycle these buffers
                    rec_rows, rec_bound = [np.array(a) for a in rows], jj
                    break
            # prefill rewrites blocks >= j through the table, so device
            # hits past the snapshot boundary are unusable SHARED blocks —
            # release them and let prefill write fresh private ones
            if len(hits) > rec_bound:
                mgr.release_all(hits[rec_bound:])
                hits = hits[:rec_bound]
            host_keys = (keys[len(hits):rec_bound] if self.has_attn else [])

        self.owned[b] = list(hits)
        self.tables[b] = 0
        self.tables[b, :len(hits)] = hits
        self._tables_dev = None
        with self._span("serve.alloc_blocks", uid=int(req.uid)):
            pre = self._take_prefetched(req.uid, shard)
            staged = self._stage_host_blocks(b, mgr, host_keys, len(hits),
                                             prefetched=pre) \
                if host_keys else 0
            self._ensure_capacity(b, L_p)

        if self.rec_prefix and rec_bound > (len(hits) + staged
                                            if self.has_attn else nb_full):
            # staging truncated under arena pressure: fall back to the
            # best boundary the staged KV coverage still supports
            rec_rows, rec_bound = None, 0
            for jj in range(len(hits) + staged, 0, -1):
                rows = self.tier.get_rec(shard, keys[jj - 1])
                if rows is not None:
                    rec_rows, rec_bound = [np.array(a) for a in rows], jj
                    break

        start_blocks = rec_bound if self.rec_prefix else len(hits) + staged
        req.prefix_hit_blocks = start_blocks

        # per-slot state
        with self._span("serve.slot_state", uid=int(req.uid)):
            self.tokens = self.tokens.at[b].set(0).at[b, :L_p].set(
                jnp.asarray(prompt, jnp.int32))
            self.n = self.n.at[b].set(L_p)
            self.cand = self.cand.at[b].set(0).at[b, 0].set(int(prompt[-1]))
        self.seq_ids[b] = req.seq_id
        self._seq_dev = None
        if _has_recurrent(self.cfg):
            self._reset_recurrent_row(b)
            if rec_rows is not None and start_blocks > 0:
                # state after positions [0, start_blocks * bs): the
                # snapshot captured at this boundary by an earlier
                # admission — a recurrent prefix hit
                self._restore_rec_row(b, rec_rows)
                self.metrics.rec_snapshot_restores += 1

        # chunked row-local prefill of the un-cached prompt tail (global
        # pool ids: local table + the slot's shard offset). Recurrent
        # archs segment the tail at registerable block boundaries so the
        # state row can be checkpointed into the tier at each one —
        # chunk decomposition is bitwise-invariant (sequential scans), so
        # tokens are unchanged; attention-only archs keep the single
        # greedy pow2 cover.
        start = start_blocks * self.block_size
        table_row = jnp.asarray(self.tables[b:b + 1] + self._table_offset(b))
        row = jnp.asarray([b], jnp.int32)
        seg_ends = ([jb * self.block_size
                     for jb in range(start_blocks + 1, nb_full + 1)]
                    if self.rec_prefix else [])
        if not seg_ends or seg_ends[-1] != L_p - 1:
            seg_ends.append(L_p - 1)
        with self._span("serve.prefill_dispatch", uid=int(req.uid)):
            for end in seg_ends:
                for C in prefill_chunks(end - start, self.prefill_chunk):
                    chunk = jnp.asarray(prompt[None, start:start + C],
                                        jnp.int32)
                    pf = self._prefill_fn(C)
                    pf_args = (self.params, self.paged, table_row, row,
                               chunk, jnp.asarray([start], jnp.int32))
                    self._contract_check("prefill", pf, pf_args)
                    self.paged = pf(*pf_args)
                    start += C
                    req.prefill_calls += 1
                    self.metrics.prefill_calls += 1
                if (self.rec_prefix and end > 0 and end == start
                        and end % self.block_size == 0
                        and end <= nb_full * self.block_size):
                    kb = end // self.block_size - 1
                    if not self.tier.has_rec(shard, keys[kb]):
                        if self.tier.put_rec(shard, keys[kb],
                                             self._collect_rec_row(b)):
                            self.metrics.rec_snapshot_captures += 1

        # publish this prompt's freshly computed full blocks (host-staged
        # ones were registered as they merged)
        if self._kv_share:
            for j in range(len(hits) + staged, nb_full):
                mgr.register(self.owned[b][j], keys[j])

        self.slots[b] = req
        self._set_poison(b, req)
        self.target[b] = L_p + req.new_tokens
        self._target_dev = None
        if self.plen[b] != L_p:
            self.plen[b] = L_p
            self._plen_dev = None
        self.reserved[b] = self._worst_case_blocks(req)
        self.n_host[b] = L_p
        self._journal("admit", uid=int(req.uid))
        kill_point("post_admit")

    # -- failure / cancellation (DESIGN.md §14) ------------------------------
    def _fail_request(self, req: Request, code: str, detail: str = "", *,
                      retryable: bool = False, fresh_stream: bool = False):
        """Retire or retry a request that hit a fault. Retryable failures
        under the retry budget requeue (original arrival order — the
        request does not lose its place); ``fresh_stream`` additionally
        derives a new noise-stream id (skipping scripted poison streams) so
        a quarantined row does not replay the same poisoned stream.
        Otherwise the request finishes with a structured ``RequestError``
        and ``result=None``."""
        if retryable and req.retries < self.request_retries:
            req.retries += 1
            self.metrics.retries += 1
            if fresh_stream:
                seed = int(req.seq_id)
                poisoned = (self.faults.poison_streams
                            if self.faults is not None else frozenset())
                while True:     # splitmix-style LCG walk over 31-bit seeds
                    seed = (seed * 6364136223846793005
                            + 1442695040888963407) % (2 ** 31)
                    if seed not in poisoned and seed != 0:
                        break
                req.noise_seed = seed
            self.queue.requeue(req)
            # the new stream id must survive a crash: replaying the retry
            # record restores determinism (seq_id keys the eps stream)
            self._journal("retry", uid=int(req.uid),
                          noise_seed=req.noise_seed, retries=req.retries)
            return
        req.error = RequestError(code, detail, retryable=retryable,
                                 attempts=req.retries + 1)
        req.result = None
        req.finish_time = time.monotonic()
        self.metrics.requests_failed += 1
        self.done.append(req)
        self._journal("fail", uid=int(req.uid), code=code)

    def _fail_slot(self, b: int, code: str, detail: str = "", *,
                   retryable: bool = False, fresh_stream: bool = False):
        """Quarantine one running slot: free it (blocks released, row
        device state cleared to the inactive no-op lane) and route its
        request through :meth:`_fail_request`. The other rows never see a
        discontinuity — slot release is exactly the path a finished
        request takes."""
        req = self.slots[b]
        assert req is not None, f"slot {b} is not occupied"
        self.slots[b] = None
        self._clear_row(b)
        self._fail_request(req, code, detail, retryable=retryable,
                           fresh_stream=fresh_stream)

    def cancel(self, uid: int) -> bool:
        """Cancel a request wherever it currently lives — queued, parked
        (parked requests sit in the queue awaiting resume), or running in a
        slot. Returns False when ``uid`` is unknown (already finished or
        never submitted). The cancelled request finishes through ``done``
        with ``error.code == "cancelled"``."""
        for req in self.queue.requests():
            if req.uid == uid:
                self.queue.remove(req)
                self._drop_prefetched(uid)
                parked = self.parked.pop(uid, None)
                if parked is not None:
                    self._discard_park(uid, parked)
                self._finalize_cancel(req)
                return True
        for s in range(self.topo.data_size):
            for i, e in enumerate(self.staged[s]):
                if e.req.uid == uid:
                    # staged but not yet adopted: the device has only a
                    # descriptor copy, and the next dispatch re-packs from
                    # these lists — dropping the entry here is exact
                    self.pool.manager(s).release_all(e.blocks)
                    self.ledger.release(s, uid)
                    del self.staged[s][i]
                    self._drop_prefetched(uid)
                    self._finalize_cancel(e.req)
                    return True
        for b in range(self.B):
            req = self.slots[b]
            if req is not None and req.uid == uid:
                self.slots[b] = None
                self._clear_row(b)
                self._finalize_cancel(req)
                return True
        return False

    def _finalize_cancel(self, req: Request):
        req.error = RequestError("cancelled", retryable=False,
                                 attempts=req.retries + 1)
        req.result = None
        req.finish_time = time.monotonic()
        self.metrics.requests_cancelled += 1
        self.done.append(req)
        self._journal("cancel", uid=int(req.uid), code="cancelled")

    # -- main loop -----------------------------------------------------------
    def _harvest_adoptions(self, adopt: np.ndarray, out_tok: np.ndarray,
                           now: float) -> tuple[int, int, int]:
        """Reconstruct the in-loop adoption chain from the packed
        ``adopt_stats`` array and replay it on the host mirrors.

        The device adopts staged descriptors in shard-major FIFO order, so
        walking descriptors ascending per shard replays adoptions in
        chronological order: at descriptor ``i`` the slot's host-side
        occupant is exactly the request the device displaced (the original
        occupant for the first adoption into a row, the previously adopted
        entry for a chain). Each displaced episode carries its terminal
        ``(n, acc, act, bad, gen)`` snapshot — finished episodes deliver
        their tokens from ``out_tokens`` (captured at displacement, before
        the buffer was overwritten), quarantined ones route through
        :meth:`_fail_request`. Mirrors for the adopted entry are installed
        WITHOUT invalidating the device caches: the device row already
        switched inside the loop, and the returned arrays are authoritative.
        Returns ``(accepted, active_row_rounds, generating_row_rounds)``
        credited to displaced episodes (the final stats array only covers
        each row's current occupant)."""
        acc_extra = act_extra = gen_extra = 0
        S = self.staging_slots
        for s in range(self.topo.data_size):
            mgr = self.pool.manager(s)
            n_adopted = 0
            for i in range(len(self.staged[s])):
                row = adopt[s * S + i]
                if row[0] < 0:
                    break               # FIFO: adopted descriptors are a prefix
                n_adopted += 1
                entry = self.staged[s][i]
                g = self.topo.global_slot(s, int(row[0]), self.B)
                ep_n, ep_acc, ep_act = int(row[1]), int(row[2]), int(row[3])
                ep_bad, ep_gen = int(row[4]), int(row[5])
                prev = self.slots[g]
                if prev is not None:
                    prev.calls_used += ep_act
                    acc_extra += ep_acc
                    act_extra += ep_act
                    gen_extra += ep_gen
                    mgr.release_all(self.owned[g])
                    self.owned[g] = []
                    self.slots[g] = None
                    if ep_bad:
                        self._fail_request(
                            prev, "nonfinite" if ep_bad & 1 else "stuck",
                            f"health bits 0b{ep_bad:02b} at n={ep_n} "
                            "(displaced in-loop)", retryable=True,
                            fresh_stream=True)
                    else:
                        prev.result = out_tok[s * S + i, :ep_n].copy()
                        prev.finish_time = now
                        self.metrics.observe_finish(prev)
                        self.done.append(prev)
                        self._journal("finish", uid=int(prev.uid),
                                      tokens=[int(t) for t in prev.result])
                req = entry.req
                req.admit_time = now
                self.ledger.release(s, req.uid)
                self.slots[g] = req
                self.owned[g] = list(entry.blocks)
                self.tables[g] = entry.table_row
                self.target[g] = entry.target
                self.plen[g] = entry.plen
                self.seq_ids[g] = req.seq_id
                self.poison[g] = entry.poison
                self.reserved[g] = len(entry.blocks)
                self.metrics.in_loop_adoptions += 1
            self.staged[s] = self.staged[s][n_adopted:]
        return acc_extra, act_extra, gen_extra

    def step(self) -> bool:
        """Admit what fits (lookahead scan, pool-pressure routing, shard
        rebalancing, priority preemption), run one device dispatch of up to
        ``rounds_per_sync`` verify rounds, harvest finished requests. The
        host touches exactly ONE small packed stats array per step — no
        ``n``/``cand`` pulls per round.

        Without staging (``staging_slots == 0``) the loop yields every
        round (``k = 1``) while admission backlog is queued, so freed
        slots refill promptly. With staging the inversion of §15 applies:
        backlog is exactly when long loops pay off (freed rows adopt
        staged descriptors WITHOUT a sync), so ``k`` comes from the
        adaptive :class:`RoundsPerSyncController` (or stays at
        ``rounds_per_sync`` when adaptivity is off and the backlog is
        staged). Returns True while there is (or may be) work left."""
        self._step_no += 1
        with self._span("serve.step"):
            return self._step()

    def _step(self) -> bool:
        """The body of :meth:`step`, one span per phase: ``serve.admit``,
        ``serve.round_dispatch``, ``serve.sync``, ``serve.harvest``."""
        with self._span("serve.admit"):
            self._poll_queue_deadlines()
            self._reconcile_staging()
            self._admit_pending()
            self._stage_pending()
            self._prefetch_queued()

        if not any(s is not None for s in self.slots):
            # _reconcile_staging unstages whenever a slot is free, so an
            # empty engine implies an empty staging area
            if self.queue:
                raise MemoryError(
                    "admission deadlock: queued request cannot fit an empty "
                    "engine (prompt+target exceeds the block pool)")
            return False

        W = self.controller.window
        staged_now = self._staged_total()
        backlog_now = len(self.queue) + staged_now
        if self.staging_slots:
            if self.adaptive_rounds:
                k = min(self.rounds_ctrl.k, self.rounds_per_sync)
            else:
                # static staging policy: stay resident while the backlog is
                # fully staged (adoption refills in-loop); an UNstaged
                # backlog still needs the host every round
                k = self.rounds_per_sync if (staged_now or not self.queue) \
                    else 1
        else:
            k = 1 if self.queue else self.rounds_per_sync
        with self._span("serve.round_dispatch"):
            for b in range(self.B):
                if self.slots[b] is not None:
                    try:
                        self._ensure_capacity(b, int(self.target[b]) + W)
                    except MemoryError as e:
                        # reservation guarantees this never fires
                        # organically; an injected alloc fault fails ONLY
                        # this slot (§14)
                        self._fail_slot(b, "capacity", str(e),
                                        retryable=True)
            if not any(s is not None for s in self.slots):
                return bool(self.queue) or self._staged_total() > 0
            adopt_dev = otok_dev = None
            round_fn = self._round_loop_fn(W, k)
            round_args = self._round_args()
            self._contract_check(
                "round" if self.staging_slots == 0 else "staged_round",
                round_fn, round_args)
            if self.staging_slots == 0:
                (self.paged, self.tokens, self.n, self.cand, stats_dev) = \
                    round_fn(*round_args)
            else:
                # staged ABI: row state comes BACK as outputs (adoption
                # mutates tables/seq/target/poison/plen in-loop) and
                # becomes the new device cache; host mirrors for adopted
                # rows are updated in the harvest walk below WITHOUT
                # invalidating these caches
                (self.paged, self._tables_dev, self.tokens, self.n,
                 self.cand, self._seq_dev, self._target_dev,
                 self._poison_dev, self._plen_dev, stats_dev, adopt_dev,
                 otok_dev) = round_fn(*round_args)
                self.metrics.staging_occupancy_hist.append(
                    staged_now / (self.topo.data_size * self.staging_slots))
        # THE host sync: one small packed int32 pull per loop
        with self._span("serve.sync"):
            adopt = None if adopt_dev is None else np.asarray(adopt_dev)
            stats = np.asarray(stats_dev)
        with self._span("serve.harvest"):
            self._harvest(stats, adopt, otok_dev, W, backlog_now)
        return True

    def _harvest(self, stats: np.ndarray, adopt: Optional[np.ndarray],
                 otok_dev, W: int, backlog_now: int) -> None:
        """Everything a step does after its sync: credit adoptions and
        rounds, retune W (and k), quarantine bad rows, finish or bound the
        rest, and checkpoint a durable engine."""
        accepted, rounds_active, n_host = stats[:, 0], stats[:, 1], stats[:, 2]
        bad = stats[:, 4]                      # §14 quarantine health bits
        rounds_exec = int(stats[:, 3].max())   # critical path across shards
        self.n_host[:] = n_host                # preemption progress mirror
        self._last_rounds_exec = rounds_exec   # run()'s convergence budget

        now = time.monotonic()
        acc_extra = act_extra = gen_extra = 0
        if adopt is not None and bool((adopt[:, 0] >= 0).any()):
            acc_extra, act_extra, gen_extra = self._harvest_adoptions(
                adopt, np.asarray(otok_dev), now)

        slot_rows = [b for b in range(self.B) if self.slots[b] is not None]
        # accumulators reset at adoption, so a row's final stats belong to
        # its CURRENT occupant; displaced episodes were credited above
        for b in slot_rows:
            self.slots[b].calls_used += int(rounds_active[b])
        act_row_rounds = act_extra + (int(rounds_active[slot_rows].sum())
                                      if slot_rows else 0)
        acc_total = acc_extra + (int(accepted[slot_rows].sum())
                                 if slot_rows else 0)
        self.metrics.observe_loop(W, rounds_exec, act_row_rounds, self.B,
                                  acc_total, backlog=backlog_now)
        if self.staging_slots:
            # W retunes from GENERATING row-rounds: forced-prefill rounds
            # accept at the prompt rate, not the stream's accept rate, and
            # would bias the window signal
            gen_total = gen_extra + (int(stats[slot_rows, 5].sum())
                                     if slot_rows else 0)
            idle_total = int(stats[:, 6].sum())
            self.metrics.idle_row_rounds += idle_total
            self.controller.observe_aggregate(acc_total, gen_total)
            self.rounds_ctrl.observe(
                rounds_exec, idle_total, self.B,
                len(self.queue) + self._staged_total())
        else:
            self.controller.observe_aggregate(acc_total, act_row_rounds)

        for b in slot_rows:
            req = self.slots[b]
            if bad[b]:
                # quarantine verdict from the packed stats: fail only this
                # slot; a retry gets a FRESH noise stream (replaying a
                # poisoned stream would just fail again)
                code = "nonfinite" if bad[b] & 1 else "stuck"
                self._fail_slot(
                    b, code, f"health bits 0b{int(bad[b]):02b} at "
                    f"n={int(n_host[b])}", retryable=True, fresh_stream=True)
                continue
            if n_host[b] >= self.target[b]:
                req.result = np.asarray(self.tokens[b, :n_host[b]])
                req.finish_time = now
                self.metrics.observe_finish(req)
                self.done.append(req)
                self._journal("finish", uid=int(req.uid),
                              tokens=[int(t) for t in req.result])
                self.slots[b] = None
                self._clear_row(b)
                continue
            if (self.max_request_rounds is not None
                    and req.calls_used >= self.max_request_rounds):
                self._fail_slot(
                    b, "round_budget", f"{req.calls_used} verify rounds "
                    f">= {self.max_request_rounds}")
                continue
            if (self.max_request_seconds is not None
                    and now - req.submit_time > self.max_request_seconds):
                self._fail_slot(
                    b, "timeout", f"{now - req.submit_time:.3f}s "
                    f"> {self.max_request_seconds}s wall time")
        # sync boundary (DESIGN.md §16): force the journal to media and
        # snapshot the scheduler, so a crash from here on recovers to
        # exactly this round's committed state
        if self.journal is not None:
            self.journal.sync()
            self._checkpoint(now)
            kill_point("post_sync")

    def run(self, max_rounds: int = 10_000) -> list[Request]:
        """Drain the queue; returns completed Requests with stats.

        ``max_rounds`` bounds *executed verify rounds* (the packed stats'
        per-sync ``loop_rounds``), not host steps — with ``rounds_per_sync
        = 4`` a per-step count would silently allow 4x the documented
        convergence budget."""
        budget = int(max_rounds)
        while (self.queue or self._staged_total()
               or any(s is not None for s in self.slots)):
            if not self.step():
                break
            budget -= self._last_rounds_exec
            if budget <= 0 and (self.queue or self._staged_total()
                                or any(s is not None for s in self.slots)):
                raise RuntimeError(
                    f"serving engine did not converge within {max_rounds} "
                    "verify rounds")
        return self.done

    def close(self) -> None:
        """Orderly shutdown of the durability layer: final checkpoint,
        journal fsync, file handles closed. A no-op for volatile engines —
        and never *required*: crash-safety is the whole point, so an
        engine that simply dies recovers identically."""
        if self.journal is not None:
            self._checkpoint()
            self.journal.close()

    # -- checkpoint / restore (DESIGN.md §16) --------------------------------
    def _checkpoint(self, now: Optional[float] = None) -> None:
        """Snapshot the scheduler at a sync boundary, atomically (temp +
        fsync + rename — a reader sees the whole snapshot or the previous
        one, never a torn JSON). What goes in: every live request's clocks
        as *elapsed durations* (``clock_export`` — monotonic stamps die
        with the process), arrival rank, retry/stream counters; for each
        parked sequence the resume snapshot (n, token row, cand row) and
        its kv chain keys — which are first force-flushed to the disk tier
        so the references are durable, not merely cached. Parked *private*
        payloads and running rows are deliberately NOT here: they are
        recomputed on restore (journaled identity + determinism makes that
        bitwise-exact), which keeps the checkpoint small and the fsync
        cheap."""
        if self._ckpt_path is None:
            return
        if now is None:
            now = time.monotonic()
        live = list(self.queue.requests())
        for s in range(self.topo.data_size):
            live += [e.req for e in self.staged[s]]
        live += [r for r in self.slots if r is not None]
        reqs = [{"uid": int(r.uid),
                 "rank": None if r._seq is None else int(r._seq),
                 "retries": int(r.retries), "noise_seed": r.noise_seed,
                 "bypassed": int(r.bypassed),
                 "queue_deadline_missed": bool(r.queue_deadline_missed),
                 "clocks": r.clock_export(now)} for r in live]
        parked = {}
        for uid, p in self.parked.items():
            if self.tier is not None and p.kv_keys:
                self.tier.flush_to_disk(p.shard, p.kv_keys)
            parked[str(int(uid))] = {
                "n": int(p.n),
                "tokens": [int(t) for t in np.asarray(p.tokens).ravel()],
                "cand": [int(t) for t in np.asarray(p.cand).ravel()],
                "nb_live": int(p.nb_live),
                "kv_keys": [int(k) for k in p.kv_keys],
                "shard": int(p.shard)}
        snap = {"version": 1, "requests": reqs, "parked": parked}
        tmp = self._ckpt_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(snap, f)
                f.flush()
                os.fsync(f.fileno())
            os.rename(tmp, self._ckpt_path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return       # degraded to journal-only recovery, never an error
        self.metrics.checkpoints_written += 1

    def _load_checkpoint(self) -> dict:
        """The latest snapshot, or {} when missing/corrupt — recovery then
        runs journal-only (full re-prefill, clocks restart at zero
        elapsed); it never errors."""
        if self._ckpt_path is None:
            return {}
        try:
            with open(self._ckpt_path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def restore(self) -> int:
        """Recover accepted-but-unfinished requests after a crash (§16).

        Replays the journal (repairing any torn tail), folds in the latest
        checkpoint, and re-enqueues every pending request with its original
        arrival rank and rebased clocks. Requests the checkpoint holds a
        parked snapshot for get a *cold* :class:`ParkedSequence` — resume
        pulls their prompt blocks back through the arena/disk fall-through
        and re-prefills only ``[covered, n-1)``; everything else re-admits
        from its journaled prompt. Either way tokens are bitwise those of
        an uninterrupted run: out = f(context, eps), and both context
        (prompt + accepted row) and eps identity (seq_id) were durable.

        Journaled *terminal* outcomes are re-delivered through ``done``:
        a crash can land between the finish record hitting the journal and
        the client draining the result, so every journaled finish (tokens
        travel in the record) and fail/cancel (error code) is surfaced
        again — at-least-once delivery, deduped by uid on the client side,
        and bitwise-identical on re-delivery by the determinism invariant.
        Returns the number of requests re-enqueued (re-deliveries not
        counted)."""
        assert self.journal is not None, "restore() requires durable_dir"
        records = RequestJournal.replay(self.journal.path,
                                        faults=self.faults)
        pending, _, delivered = RequestJournal.pending(records)
        ckpt = self._load_checkpoint()
        by_uid = {int(r["uid"]): r for r in ckpt.get("requests", [])}
        snaps = {int(u): p for u, p in ckpt.get("parked", {}).items()}
        now = time.monotonic()
        max_rank = -1
        recovered = 0
        # original queue order: ranked submits first, by rank
        for uid, rec in sorted(
                pending.items(),
                key=lambda kv: (kv[1].get("rank") is None,
                                kv[1].get("rank") or 0)):
            req = Request(uid=int(uid),
                          prompt=np.asarray(rec["prompt"], np.int64),
                          new_tokens=int(rec["new_tokens"]),
                          priority=int(rec.get("priority", 0)),
                          deadline=rec.get("deadline"),
                          noise_seed=rec.get("noise_seed"))
            req.retries = int(rec.get("retries", 0))
            req._seq = None if rec.get("rank") is None else int(rec["rank"])
            c = by_uid.get(req.uid)
            if c is not None:
                req.bypassed = int(c.get("bypassed", 0))
                req.queue_deadline_missed = bool(
                    c.get("queue_deadline_missed", False))
                req.clock_rebase(c.get("clocks", {}), now)
            else:
                req.submit_time = now     # journal-only: clock restarts
            if req._seq is None:
                req._seq = max_rank + 1
            max_rank = max(max_rank, req._seq)
            snap = snaps.get(req.uid)
            if snap is not None and rec.get("parked"):
                self.parked[req.uid] = ParkedSequence(
                    n=int(snap["n"]),
                    tokens=np.asarray(snap["tokens"], np.int32),
                    cand=np.asarray(snap["cand"], np.int32),
                    nb_live=int(snap["nb_live"]),
                    kv_keys=tuple(int(k) for k in snap["kv_keys"]),
                    shard=int(snap["shard"]), cold=True)
                self.metrics.recovered_parked += 1
            self.queue.requeue(req)       # rank pinned: original order
            recovered += 1
        self.queue.advance_seq(max_rank)
        self.metrics.recovered_requests += recovered
        # re-deliver journaled outcomes whose pickup the crash may have
        # swallowed (see docstring); no journal write — these records are
        # already terminal, replaying them again is idempotent
        for uid, rec in delivered.items():
            req = Request(uid=int(uid),
                          prompt=np.asarray(rec["prompt"], np.int64),
                          new_tokens=int(rec["new_tokens"]),
                          priority=int(rec.get("priority", 0)),
                          deadline=rec.get("deadline"),
                          noise_seed=rec.get("noise_seed"))
            if rec["terminal"] == "finish" and "tokens" in rec:
                req.result = np.asarray(rec["tokens"], np.int32)
            else:
                req.error = RequestError(
                    rec.get("code", rec["terminal"]), "re-delivered (§16)")
            self.done.append(req)
        return recovered

    # -- telemetry -----------------------------------------------------------
    def export_metrics(self) -> dict:
        out = self.metrics.export(
            self.pool.stats_export(),
            self.tier.stats_export() if self.tier is not None else None)
        out["blocks_in_use"] = self.pool.blocks_in_use()
        out["blocks_available"] = self.pool.available()
        out["parked_requests"] = len(self.parked)
        out["queue_depth"] = len(self.queue)
        out["staged_requests"] = self._staged_total()
        out["prefetched_requests"] = len(self._prefetched)
        out["rounds_per_sync_final"] = (self.rounds_ctrl.k
                                        if self.staging_slots
                                        else self.rounds_per_sync)
        # §14 failure counters are always present (chaos-job assertions):
        # tier-backed ones default to 0 when no tier is configured
        out.setdefault("checksum_failures", 0)
        out.setdefault("tier_tripped", 0)
        out.setdefault("tier_state", "closed")
        out.setdefault("tier_denied_ops", 0)
        out["faults_injected"] = (self.faults.total_fired
                                  if self.faults is not None else 0)
        if self.faults is not None:
            # per-seam fired counts (zero-filled over every known seam) so
            # a chaos run shows WHICH seams actually exercised (§14/§16)
            out.update(self.faults.fired_export())
        # durability observability (§16): disk breaker + journal counters
        # present whenever configured; zero-filled defaults otherwise so
        # the recovery CI job can assert on them unconditionally
        if (self.disk is not None
                and (self.tier is None or self.tier.disk is None)):
            out.update(self.disk.stats_export())
        out.setdefault("disk_state", "closed")
        out.setdefault("disk_tripped", 0)
        out.setdefault("disk_hits", 0)
        out.setdefault("disk_promotes", 0)
        out.setdefault("disk_spills", 0)
        if self.journal is not None:
            out.update(self.journal.stats_export())
        if self.topo.data_size > 1:
            out["blocks_available_by_shard"] = [
                self.pool.available(s) for s in range(self.topo.data_size)]
        return out
