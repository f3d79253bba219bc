"""Training launcher: ``python -m repro.launch.train --arch qwen3-1.7b
--reduced --steps 50``.

Builds mesh + sharding rules, jits the train step with explicit
in/out_shardings, streams the synthetic token pipeline, checkpoints
periodically. The same ``make_train_step`` is lowered (never executed) by
the multi-pod dry-run.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import optim
from repro.checkpoint import latest_step, restore_pytree, save_pytree
from repro.configs import get_config
from repro.data.pipeline import ShardedTokenPipeline
from repro.data.synthetic import token_batches
from repro.models import frontends
from repro.models.losses import lm_loss
from repro.models.transformer import TransformerLM
from repro.launch.mesh import auto_mesh
from repro.sharding import use_rules
from repro.sharding.rules import (batch_sharding, default_activation_rules,
                                  param_shardings, replicated)


def make_optimizer(cfg, steps: int = 10_000, peak_lr: float = 3e-4):
    """Adafactor for the >=100B configs (AdamW fp32 moments for 671B exceed
    16 GB/chip x 256 — DESIGN.md §4); AdamW otherwise."""
    sched = optim.linear_warmup_cosine(peak_lr, min(1000, steps // 10 + 1),
                                       steps)
    big = cfg.d_model >= 6144
    return optim.adafactor(sched) if big else optim.adamw(sched)


def make_train_step(cfg, optimizer, remat: bool = True,
                    prefix_embeddings: bool = None, accum_steps: int = 1):
    """``accum_steps > 1``: gradient accumulation over microbatches (the
    batch's leading dim is split), bounding activation memory at
    1/accum_steps of the global batch (§Perf A6)."""
    has_prefix = cfg.n_prefix_tokens > 0

    def grads_of(params, batch, prefix_emb):
        def loss_fn(p):
            return lm_loss(p, cfg, batch,
                           prefix_emb if has_prefix else None,
                           remat=remat)
        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(params, opt_state, batch, prefix_emb=None):
        if accum_steps == 1:
            (loss, metrics), grads = grads_of(params, batch, prefix_emb)
        else:
            B = batch.shape[0]
            assert B % accum_steps == 0
            mb = batch.reshape(accum_steps, B // accum_steps,
                               *batch.shape[1:])
            pe = (None if prefix_emb is None else
                  prefix_emb.reshape(accum_steps, B // accum_steps,
                                     *prefix_emb.shape[1:]))

            def body(acc, xs):
                (l, m), g = grads_of(params, xs[0],
                                     xs[1] if pe is not None else None)
                g32 = jax.tree.map(lambda a: a.astype(jnp.float32), g)
                acc_g, acc_l = acc
                return (jax.tree.map(jnp.add, acc_g, g32), acc_l + l), m

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            xs = (mb, pe) if pe is not None else (mb, mb)
            (gsum, lsum), metrics = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)), xs)
            grads = jax.tree.map(lambda a: a / accum_steps, gsum)
            loss = lsum / accum_steps
            metrics = jax.tree.map(lambda a: a[-1], metrics)

        grads = optim.zero_frozen(grads)
        grads, gnorm = optim.clip_by_global_norm(grads, 1.0)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        metrics = dict(metrics, grad_norm=gnorm)
        return params, opt_state, metrics

    return train_step


def shard_jit_train_step(cfg, optimizer, mesh, batch_shape, remat=True,
                         accum_steps=None):
    """jit with explicit shardings, using abstract params (no allocation)."""
    import os as _os
    if accum_steps is None:
        # §Perf A6 default: microbatch the >=100B-class models (4-way) —
        # activation memory scales 1/accum (387->69 GB/dev on jamba train).
        default = "4" if cfg.d_model >= 6144 else "1"
        accum_steps = int(_os.environ.get("REPRO_ACCUM_STEPS", default))
    no_tp = _os.environ.get("REPRO_NO_TP") == "1"
    params_shape = jax.eval_shape(
        lambda: TransformerLM.init(jax.random.PRNGKey(0), cfg))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    p_shard = param_shardings(params_shape, mesh, no_tp=no_tp)
    o_shard = param_shardings(opt_shape, mesh, no_tp=no_tp)
    b_shard = batch_sharding(mesh, no_tp=no_tp)
    step = make_train_step(cfg, optimizer, remat=remat,
                           accum_steps=accum_steps)

    in_sh = (p_shard, o_shard, b_shard)
    args = [params_shape, opt_shape,
            jax.ShapeDtypeStruct(batch_shape, jnp.int32)]
    if cfg.n_prefix_tokens:
        in_sh = in_sh + (b_shard,)
        args.append(frontends.prefix_spec(cfg, batch_shape[0]))
    jitted = jax.jit(step, in_shardings=in_sh,
                     out_shardings=(p_shard, o_shard, replicated(mesh)))
    return jitted, args, (p_shard, o_shard)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    n_dev = len(jax.devices())
    mesh = auto_mesh((n_dev, 1), ("data", "model"))
    optimizer = make_optimizer(cfg, args.steps, args.lr)

    key = jax.random.PRNGKey(0)
    params = TransformerLM.init(key, cfg)
    opt_state = optimizer.init(params)
    start = 0
    if args.ckpt_dir and (s := latest_step(args.ckpt_dir)) is not None:
        params = restore_pytree(args.ckpt_dir, s)
        start = s
        print(f"restored step {s}")

    step_fn = jax.jit(make_train_step(cfg, optimizer, remat=False))
    pipe = ShardedTokenPipeline(
        token_batches(max(512, args.batch * 8), args.batch, args.seq,
                      cfg.vocab), mesh)
    rules = default_activation_rules(mesh)

    with mesh, use_rules(mesh, rules):
        t0 = time.time()
        for it, batch in zip(range(start, args.steps), pipe):
            pre = (frontends.random_prefix(jax.random.fold_in(key, it), cfg,
                                           args.batch)
                   if cfg.n_prefix_tokens else None)
            if pre is not None:
                params, opt_state, m = step_fn(params, opt_state, batch, pre)
            else:
                params, opt_state, m = step_fn(params, opt_state, batch)
            if (it + 1) % args.log_every == 0:
                dt = (time.time() - t0) / args.log_every
                print(f"step {it+1} loss {float(m['loss']):.4f} "
                      f"xent {float(m['xent']):.4f} {dt*1e3:.0f} ms/step")
                t0 = time.time()
            if args.ckpt_dir and (it + 1) % args.ckpt_every == 0:
                save_pytree(params, args.ckpt_dir, it + 1)
    print("done")


if __name__ == "__main__":
    main()
