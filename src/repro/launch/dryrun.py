import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
#   Set ONLY here — smoke tests and benches see the real single CPU device.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) pair, lower + compile the real step
function against the production mesh — 16x16 single-pod AND 2x16x16
multi-pod — with abstract (ShapeDtypeStruct) params: no allocation, but full
SPMD partitioning, memory analysis and cost analysis. Failures here
(sharding mismatch, OOM at compile, unsupported collective) are bugs.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
      --shape train_4k [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all   # full sweep

Artifacts land in benchmarks/artifacts/dryrun/<arch>__<shape>__<mesh>.json
(flops, bytes, per-collective byte totals, memory analysis) — the roofline
analysis (benchmarks/roofline.py) consumes them.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.launch.train import make_optimizer, shard_jit_train_step
from repro.launch.serve import make_serve_step
from repro.models import frontends
from repro.models.transformer import TransformerLM
from repro.sharding import use_rules
from repro.sharding.rules import (batch_sharding, cache_shardings,
                                  default_activation_rules,
                                  param_shardings, replicated)
from jax.sharding import NamedSharding, PartitionSpec as P

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")

DECODE_WINDOW = 8

from repro.analysis import parse_collective_bytes


def abstract_params(cfg):
    return jax.eval_shape(
        lambda: TransformerLM.init(jax.random.PRNGKey(0), cfg))


def lower_train(cfg, shape, mesh):
    opt = make_optimizer(cfg)
    jitted, args, _ = shard_jit_train_step(
        cfg, opt, mesh, (shape.global_batch, shape.seq_len), remat=True)
    return jitted.lower(*args)


def lower_prefill(cfg, shape, mesh):
    params_shape = abstract_params(cfg)
    p_shard = param_shardings(params_shape, mesh)
    B = shape.global_batch
    b_shard = batch_sharding(mesh)

    def prefill_step(params, tokens, prefix_emb=None):
        # prefill uses bounded MoE capacity (2.0): no-drop C=N*k at 1M-token
        # prefill is a 100x memory/flops blowup; the engine's decode windows
        # (small N) stay exact no-drop. See EXPERIMENTS.md §Dry-run.
        logits, h, _ = TransformerLM.apply(params, cfg, tokens, prefix_emb,
                                           moe_capacity=2.0)
        return logits[:, -1]

    args = [params_shape,
            jax.ShapeDtypeStruct((B, shape.seq_len), jnp.int32)]
    in_sh = [p_shard, b_shard]
    if cfg.n_prefix_tokens:
        args.append(frontends.prefix_spec(cfg, B))
        in_sh.append(b_shard)
    vshard = "model" if cfg.vocab % mesh.shape["model"] == 0 else None
    jitted = jax.jit(prefill_step, in_shardings=tuple(in_sh),
                     out_shardings=NamedSharding(
                         mesh, P(_dp(mesh) if B % _dp_size(mesh) == 0
                                 else None, vshard)))
    return jitted.lower(*args)


def _dp(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def _dp_size(mesh):
    if "pod" in mesh.axis_names:
        return mesh.shape["pod"] * mesh.shape["data"]
    return mesh.shape["data"]


def lower_decode(cfg, shape, mesh):
    from repro.sharding import rules as rules_mod
    rules_mod.MOE_INFERENCE_LAYOUT = (
        os.environ.get("REPRO_MOE_EP", "1") == "1")
    params_shape = abstract_params(cfg)
    p_shard = param_shardings(params_shape, mesh)
    rules_mod.MOE_INFERENCE_LAYOUT = False
    B, S, W = shape.global_batch, shape.seq_len, DECODE_WINDOW
    dtype = cfg.param_dtype
    # §Perf C1: round the cache length up to a multiple of 256 so the
    # sequence dim is mesh-divisible -> caches shard over "model" on S
    # (flash-decode/sequence-parallel attention) instead of being gathered.
    S_cache = -(-(S + W) // 256) * 256
    cache_shape = jax.eval_shape(
        lambda: TransformerLM.init_cache(cfg, B, S_cache, dtype))
    c_shard = cache_shardings(cache_shape, mesh, B)
    dp_ok = B % _dp_size(mesh) == 0
    bspec = P(_dp(mesh)) if dp_ok else P(None)
    lowmem = os.environ.get("REPRO_LOWMEM_DECODE", "0") == "1"
    step = make_serve_step(cfg, window=W, low_memory=lowmem)
    args = [params_shape,
            jax.ShapeDtypeStruct((B, W), jnp.int32),
            cache_shape,
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B, W, cfg.vocab), jnp.float32)]
    in_sh = (p_shard,
             NamedSharding(mesh, P(*bspec, None)),
             c_shard,
             NamedSharding(mesh, bspec),
             NamedSharding(mesh, P(*bspec, None,
                                   "model" if cfg.vocab
                                   % mesh.shape["model"] == 0 else None)))
    out_cache_shape = (cache_shape if lowmem else
                       jax.eval_shape(lambda c: TransformerLM.select_states(
                           cfg, c, jnp.ones((B,), jnp.int32)),
                           _window_cache_shape(cfg, B, S_cache, W, dtype)))
    out_sh = (NamedSharding(mesh, P(*bspec, None)),
              NamedSharding(mesh, bspec),
              cache_shardings(out_cache_shape, mesh, B))
    jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
    return jitted.lower(*args)


def _window_cache_shape(cfg, B, S, W, dtype):
    """Shape of decode_window's new_cache (per-position recurrent states)."""
    cache = jax.eval_shape(
        lambda: TransformerLM.init_cache(cfg, B, S, dtype))
    return jax.eval_shape(
        lambda p, c: TransformerLM.decode_window(
            p, cfg, jnp.zeros((B, W), jnp.int32), c,
            jnp.zeros((B,), jnp.int32))[2],
        abstract_params(cfg), cache)


def run_pair(arch: str, shape_name: str, multi_pod: bool, out_dir: str):
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}"
    path = os.path.join(out_dir, tag + ".json")
    ok, reason = shape_applicable(arch, shape_name)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": reason}
        json.dump(rec, open(path, "w"), indent=1)
        print(f"[skip] {tag}: {reason}")
        return rec

    cfg = get_config(arch)
    kb = os.environ.get("REPRO_OVERRIDE_BLOCKS")
    if kb is not None:
        # roofline scan-correction probe: same config at k scanned blocks
        import dataclasses
        k = int(kb)
        cfg = dataclasses.replace(
            cfg, n_layers=(len(cfg.layer_prefix) + k * len(cfg.layer_block)
                           + len(cfg.layer_suffix)))
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_activation_rules(
        mesh, shard_embed=os.environ.get("REPRO_SHARD_EMBED") == "1",
        no_tp=os.environ.get("REPRO_NO_TP") == "1")
    if (shape.kind == "decode"
            and os.environ.get("REPRO_MOE_EP", "1") == "1"):
        m = dict(rules.mapping)
        m["_moe_ep"] = True
        from repro.sharding.api import Rules
        rules = Rules(m)
    t0 = time.time()
    try:
        with mesh, use_rules(mesh, rules):
            if shape.kind == "train":
                lowered = lower_train(cfg, shape, mesh)
            elif shape.kind == "prefill":
                lowered = lower_prefill(cfg, shape, mesh)
            else:
                lowered = lower_decode(cfg, shape, mesh)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        cost = compiled.cost_analysis() or {}
        try:
            mem = compiled.memory_analysis()
            mem_rec = {
                "argument_size": getattr(mem, "argument_size_in_bytes", None),
                "output_size": getattr(mem, "output_size_in_bytes", None),
                "temp_size": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_size": getattr(
                    mem, "generated_code_size_in_bytes", None),
            }
        except Exception as e:  # noqa: BLE001
            mem_rec = {"error": str(e)}
        hlo = compiled.as_text()
        coll = parse_collective_bytes(hlo)
        rec = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "ok",
            "n_devices": int(mesh.devices.size),
            "flops": float(cost.get("flops", -1.0)),
            "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
            "collectives": coll,
            "memory": mem_rec,
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "decode_window": DECODE_WINDOW if shape.kind == "decode" else None,
        }
        json.dump(rec, open(path, "w"), indent=1)
        print(f"[ok] {tag}: flops={rec['flops']:.3e} "
              f"bytes={rec['bytes_accessed']:.3e} "
              f"coll={sum(c['bytes'] for c in coll.values()):.3e}B "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        return rec
    except Exception as e:  # noqa: BLE001
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "error", "error": str(e)[:2000],
               "trace": traceback.format_exc()[-4000:]}
        json.dump(rec, open(path, "w"), indent=1)
        print(f"[ERR] {tag}: {str(e)[:200]}")
        return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep all (arch, shape) x both meshes")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(ART_DIR))
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    use_compile_cache()

    if args.all:
        jobs = [(a, s, mp)
                for a in ARCHS for s in SHAPES
                for mp in (False, True)]
    else:
        assert args.arch and args.shape
        jobs = [(args.arch, args.shape, args.multi_pod)]

    n_err = 0
    for arch, shape_name, mp in jobs:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        path = os.path.join(args.out,
                            f"{arch}__{shape_name}__{mesh_name}.json")
        if args.skip_existing and os.path.exists(path):
            rec = json.load(open(path))
            if rec.get("status") in ("ok", "skipped"):
                print(f"[cached] {arch}__{shape_name}__{mesh_name}")
                continue
        rec = run_pair(arch, shape_name, mp, args.out)
        n_err += rec["status"] == "error"
    print(f"dry-run sweep complete; errors: {n_err}")
    return n_err


if __name__ == "__main__":
    raise SystemExit(main())
