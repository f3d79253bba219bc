"""Paged KV-cache serving runtime with adaptive speculation and telemetry.

See DESIGN.md §6-12 and ``repro.serving.engine.ServingEngine`` for the
architecture; ``repro.engine.ContinuousBatcher`` remains as a thin
compatibility alias over this subsystem. ``ServingTopology`` maps an engine
onto a device mesh (per-data-shard slot ranges + block sub-pools, shard_map
round step); ``ShardedBlockPool`` routes admissions by pool pressure and
carries the sequence-migration block accounting; under saturation the
engine schedules with admission lookahead, priority preemption (host-side
parking + bitwise-exact resume, ``ParkedSequence``), and shard rebalancing
(§12); fault isolation (§14) quarantines failures per request
(``RequestError``), integrity-checks the host cache tiers behind a
``CircuitBreaker``, and scripts every failure path deterministically
through a ``FaultPlan``; durability (§16) journals the request lifecycle
(``RequestJournal``), spills arena victims to a ``DiskTier``, and
checkpoints the scheduler so a SIGKILLed engine restarts bitwise-exact
(``REPRO_KILL_POINT`` crash harness). Telemetry is ``EngineMetrics``'
counters and a bounded ``SpanLog`` of the engine's host phases.
"""
from repro.serving.admission import (AdmissionQueue, Request, pow2_at_most,
                                     prefill_chunks)
from repro.serving.adaptive import AdaptiveWindowController
from repro.serving.blocks import BlockManager, ShardedBlockPool, chain_hashes
from repro.serving.engine import ParkedSequence, ServingEngine
from repro.serving.faults import (KILL_POINTS, CircuitBreaker, FaultPlan,
                                  RequestError, StagingFault, kill_point)
from repro.serving.hostcache import (DiskTier, HostArena, HostTier,
                                     StagingRing)
from repro.serving.journal import RequestJournal
from repro.serving.metrics import (EngineMetrics, Span, SpanLog,
                                   default_span_log, percentile)
from repro.serving.topology import ServingTopology

__all__ = ["AdmissionQueue", "Request", "prefill_chunks", "pow2_at_most",
           "AdaptiveWindowController", "BlockManager", "ShardedBlockPool",
           "chain_hashes", "ParkedSequence", "ServingEngine",
           "EngineMetrics", "Span", "SpanLog", "default_span_log",
           "percentile", "ServingTopology",
           "HostArena", "HostTier", "StagingRing", "DiskTier",
           "RequestJournal", "KILL_POINTS", "kill_point",
           "CircuitBreaker", "FaultPlan", "RequestError", "StagingFault"]
