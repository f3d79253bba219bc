"""Serving launcher: predictive sampling through the paged serving runtime.

``python -m repro.launch.serve --arch qwen3-1.7b --reduced --requests 6``

Drives ``repro.serving.ServingEngine`` (paged KV blocks, prefix cache,
adaptive speculation window, telemetry). ``--no-adaptive`` pins the window;
``--no-prefix-cache`` disables block sharing; ``--mesh data[,model]`` runs
the engine on a device mesh (``ServingTopology``: per-data-shard slot
ranges + block sub-pools, shard_map round step; params replicated over
data and — when model > 1 — tensor-sharded via
``serving_param_shardings``); ``--no-donate`` disables round-buffer
donation (A/B for the copy-per-round cost); ``--lookahead`` /
``--max-head-bypass`` / ``--no-preempt`` / ``--preempt-floor`` /
``--no-rebalance`` tune the saturation-safe scheduler (DESIGN.md §12:
lookahead admission, priority preemption with exact resume, shard
rebalancing by sequence migration); ``--staging-slots`` /
``--adaptive-rounds`` turn on device-resident continuous batching
(DESIGN.md §15: pre-staged prompts adopted into freed rows inside the
round loop, rounds_per_sync retuned from idle row-rounds);
``--durable-dir`` / ``--journal-fsync-every`` / ``--no-disk-tier`` turn on
crash-safe serving (DESIGN.md §16: write-ahead request journal, scheduler
checkpoints, disk tier below the host arena — a relaunched engine with the
same ``--durable-dir`` recovers every accepted request bitwise-exactly).

Also exports ``make_serve_step`` — the W-token verify step the multi-pod
dry-run lowers for the decode shapes (decode_32k / long_500k).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.reparam import reparam_argmax
from repro.launch.compile_cache import use_compile_cache
from repro.models.transformer import TransformerLM
from repro.serving import (FaultPlan, Request, ServingEngine,
                           ServingTopology)


def make_serve_step(cfg, window: int = 8, low_memory: bool = False):
    """One predictive-sampling verify round (dry-run unit for decode shapes).

    Args: params, cand (B, W), cache, cache_len (B,), eps (B, W, V).
    Returns (out tokens (B, W), accept (B,), new_cache).

    ``low_memory`` (§Perf C4): two-pass variant for recurrent/hybrid archs —
    pass 1 computes logits without materializing per-position states
    (DCE'd); pass 2 re-advances the states with a freeze-masked scan to the
    accept point. Trades ~2x decode compute for O(layers x B x W x state)
    memory (the 101 GB/dev jamba-decode term).
    """
    def serve_step(params, cand, cache, cache_len, eps):
        logits, h, new_cache = TransformerLM.decode_window(
            params, cfg, cand, cache, cache_len,
            state_mode="none" if low_memory else "per_position")
        out = reparam_argmax(logits.astype(jnp.float32), eps)
        match = cand[:, 1:] == out[:, :-1]
        accept = 1 + jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                             axis=1)
        if low_memory:
            _, _, adv = TransformerLM.decode_window(
                params, cfg, cand, cache, cache_len,
                state_mode="advance", accept=accept)
            return out, accept, adv
        sel = TransformerLM.select_states(cfg, new_cache, accept)
        return out, accept, sel

    return serve_step


def make_serving_topology(mesh_arg: str):
    """``--mesh data[,model]`` -> ``ServingTopology`` over a host mesh.

    Requires ``data * model`` visible devices (force with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` on CPU)."""
    from repro.launch.mesh import make_host_mesh

    try:
        parts = [int(p) for p in mesh_arg.split(",")]
    except ValueError:
        parts = []
    if not 1 <= len(parts) <= 2:
        raise SystemExit(f"--mesh wants DATA or DATA,MODEL, got {mesh_arg!r}")
    data, model = (parts + [1])[:2]
    n = len(jax.devices())
    if data * model > n:
        raise SystemExit(
            f"--mesh {mesh_arg} needs {data * model} devices, have {n} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=...)")
    return ServingTopology(make_host_mesh(data, model))


def place_params(params, topo: ServingTopology):
    """Replicate params over data; tensor-shard over model when present."""
    if topo.mesh is None:
        return params
    from repro.sharding.rules import replicated, serving_param_shardings

    if all(topo.mesh.shape[a] == 1 for a in topo.auto_axes):
        return jax.device_put(params, replicated(topo.mesh))
    shapes = jax.eval_shape(lambda: params)
    return jax.device_put(params, serving_param_shardings(shapes, topo.mesh))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--window", type=int, default=8,
                    help="max verify window W (adaptive controller's bound)")
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV-cache block size (tokens per physical block)")
    ap.add_argument("--no-adaptive", action="store_true",
                    help="pin W instead of adapting it to acceptance")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--mesh", default=None, metavar="DATA[,MODEL]",
                    help="run on a device mesh, e.g. --mesh 2 or --mesh 4,2")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable round-buffer donation (keeps the old "
                         "copy-per-round behaviour; for A/B measurement)")
    ap.add_argument("--rounds-per-sync", type=int, default=4,
                    help="device-resident verify rounds per host sync "
                         "(lax.while_loop trip bound; 1 = host-driven; "
                         "with --adaptive-rounds this is the k_max bound)")
    ap.add_argument("--staging-slots", type=int, default=0,
                    help="queued requests pre-staged per shard for "
                         "in-loop slot adoption (DESIGN.md §15: freed "
                         "rows adopt staged work mid-loop, no sync to "
                         "refill); 0 = host-only admission, compiles the "
                         "legacy round program byte-identically")
    ap.add_argument("--adaptive-rounds", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="retune rounds_per_sync from the idle row-round "
                         "EWMA the way W is retuned from acceptance "
                         "(default: on exactly when staging is on; "
                         "requires --staging-slots > 0)")
    ap.add_argument("--lookahead", type=int, default=8,
                    help="admission lookahead depth: queued requests "
                         "scanned past an unroutable head (1 = the old "
                         "head-of-line-blocking admission)")
    ap.add_argument("--max-head-bypass", type=int, default=16,
                    help="aging bound: admissions allowed to jump the "
                         "queue head before admission goes head-only")
    ap.add_argument("--no-preempt", action="store_true",
                    help="disable priority preemption (parking lower-"
                         "priority slots for a higher-priority head)")
    ap.add_argument("--preempt-floor", type=float, default=0.75,
                    help="progress floor: running slots past this fraction "
                         "of their generation target are never preempted")
    ap.add_argument("--no-rebalance", action="store_true",
                    help="disable shard rebalancing (sequence migration "
                         "between block sub-pools at admission)")
    ap.add_argument("--host-cache-mb", type=float, default=None,
                    metavar="MB",
                    help="host cache tier byte budget in MiB (DESIGN.md "
                         "§13: spilled prefix blocks, parked sequences, "
                         "recurrent-state snapshots share one bounded LRU "
                         "arena); default: REPRO_HOST_CACHE_MB or 256")
    ap.add_argument("--no-host-cache", action="store_true",
                    help="disable the host cache tier (evicted prefix "
                         "blocks drop, parked payloads stay raw host "
                         "copies, recurrent archs never prefix-hit)")
    ap.add_argument("--max-request-seconds", type=float, default=None,
                    metavar="S",
                    help="per-request wall-time bound (DESIGN.md §14): a "
                         "request running past this fails with a "
                         "structured 'timeout' error instead of holding "
                         "its slot forever")
    ap.add_argument("--request-retries", type=int, default=0,
                    help="re-admissions granted after a retryable "
                         "per-request failure (quarantined row, admission "
                         "fault) before the request fails for good")
    ap.add_argument("--no-integrity-checks", action="store_true",
                    help="skip host-tier checksum stamping/verification "
                         "(DESIGN.md §14; corruption then goes undetected "
                         "— A/B for the checksum cost)")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="deterministic fault-injection plan, e.g. "
                         "'seed=7,alloc=@2;5,arena_corrupt=0.05,poison=3' "
                         "(default: REPRO_FAULT_PLAN env)")
    ap.add_argument("--durable-dir", default=None, metavar="DIR",
                    help="crash-safety root (DESIGN.md §16): write-ahead "
                         "request journal, scheduler checkpoints at sync "
                         "boundaries, and the disk tier below the host "
                         "arena live here; a restarted engine with the "
                         "same DIR recovers every accepted request "
                         "bitwise-exactly. Default: volatile engine")
    ap.add_argument("--journal-fsync-every", type=int, default=1,
                    metavar="N",
                    help="fsync the request journal every N records "
                         "(1 = an accepted submit is durable before "
                         "submit() returns; larger batches the fsync cost "
                         "with an exposure window of at most N-1 records "
                         "past the last sync boundary)")
    ap.add_argument("--no-disk-tier", action="store_true",
                    help="with --durable-dir: keep journal + checkpoint "
                         "but skip the disk tier (arena LRU victims drop "
                         "instead of spilling; restarts re-prefill every "
                         "prefix instead of re-hitting it on disk)")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    params = TransformerLM.init(jax.random.PRNGKey(0), cfg)
    topo = ServingTopology() if args.mesh is None \
        else make_serving_topology(args.mesh)
    params = place_params(params, topo)
    engine = ServingEngine(cfg, params, batch=args.batch,
                           window_max=args.window, max_len=args.max_len,
                           eps_key=jax.random.PRNGKey(1),
                           block_size=args.block_size,
                           adaptive=not args.no_adaptive,
                           prefix_cache=not args.no_prefix_cache,
                           topology=topo, donate=not args.no_donate,
                           rounds_per_sync=args.rounds_per_sync,
                           staging_slots=args.staging_slots,
                           adaptive_rounds=args.adaptive_rounds,
                           lookahead=args.lookahead,
                           max_head_bypass=args.max_head_bypass,
                           preempt=not args.no_preempt,
                           preempt_floor=args.preempt_floor,
                           rebalance=not args.no_rebalance,
                           host_cache_mb=(0 if args.no_host_cache
                                          else args.host_cache_mb),
                           max_request_seconds=args.max_request_seconds,
                           request_retries=args.request_retries,
                           integrity_checks=not args.no_integrity_checks,
                           faults=(FaultPlan.parse(args.fault_plan)
                                   if args.fault_plan else None),
                           durable_dir=args.durable_dir,
                           journal_fsync_every=args.journal_fsync_every,
                           disk_tier=not args.no_disk_tier)
    if args.durable_dir:
        recovered = engine.restore()
        if recovered:
            print(f"recovered {recovered} journaled requests from "
                  f"{args.durable_dir}")
    if topo.mesh is not None:
        print(f"serving on {topo}")
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        engine.submit(Request(
            uid=i, prompt=rng.integers(0, cfg.vocab,
                                       size=int(rng.integers(2, 8))),
            new_tokens=args.new_tokens))
    t0 = time.time()
    done = engine.run()
    dt = time.time() - t0
    engine.close()
    m = engine.export_metrics()
    # rejected submits and failed requests are delivered through ``done``
    # too, with ``error`` set and no result
    failed = [r for r in done if r.error is not None]
    served = [r for r in done if r.error is None]
    total_new = sum(r.new_tokens for r in served)
    print(f"served {len(served)} requests / {total_new} tokens "
          f"in {m['rounds']} verify rounds ({dt:.1f}s)")
    print(f"ARM calls vs ancestral baseline: "
          f"{100.0 * m['arm_calls_vs_ancestral']:.1f}% "
          f"(paged engine, W<= {args.window}, "
          f"adaptive={not args.no_adaptive})")
    print("telemetry: " + json.dumps(
        {k: (round(v, 4) if isinstance(v, float) else v)
         for k, v in m.items()}, indent=2))
    for r in served[:3]:
        print(f"  req {r.uid}: calls={r.calls_used} "
              f"prefill={r.prefill_calls} tokens={r.result[:12]}…")
    for r in failed:
        print(f"request {r.uid} failed: {r.error}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
