"""Pallas TPU kernel: fused Gumbel-max verify over vocab tiles.

The hot loop of predictive sampling's verify step is
``argmax_v(logits[w, v] + eps[w, v])`` over a 32k-262k vocab for each of the
W window slots. On GPU the paper computed a log-softmax first; on TPU we
exploit LSE-shift invariance and never normalize (DESIGN.md §3) — the kernel
is a pure bandwidth-bound tiled reduction:

  grid = (R / br, V / bv); for each row tile, vocab tiles stream through
  VMEM while a running (max, argmax) pair lives in VMEM scratch (persists
  across the sequential TPU grid). bv is lane-aligned (multiple of 128);
  ties resolve to the lowest index (strict-greater update), matching
  jnp.argmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -3.0e38  # python float: pallas kernels must not capture array consts


def _verify_kernel(logits_ref, eps_ref, out_ref, m_ref, a_ref, *, bv: int):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], NEG)
        a_ref[...] = jnp.zeros_like(a_ref[...])

    vals = (logits_ref[...].astype(jnp.float32)
            + eps_ref[...].astype(jnp.float32))          # (br, bv)
    blk_max = jnp.max(vals, axis=1, keepdims=True)       # (br, 1)
    blk_arg = (jnp.argmax(vals, axis=1, keepdims=True).astype(jnp.int32)
               + j * bv)

    run_max = m_ref[...]
    take = blk_max > run_max                             # strict: first wins
    m_ref[...] = jnp.where(take, blk_max, run_max)
    a_ref[...] = jnp.where(take, blk_arg, a_ref[...])

    @pl.when(j == nv - 1)
    def _emit():
        out_ref[...] = a_ref[...]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_vocab",
                                             "interpret"))
def spec_verify_kernel(logits, eps, block_rows: int = 8,
                       block_vocab: int = 1024, interpret: bool = True):
    """argmax(logits + eps, axis=-1) for logits, eps: (R, V) -> (R,) int32."""
    R, V = logits.shape
    br = min(block_rows, R)
    bv = min(block_vocab, V)
    Rp = -(-R // br) * br
    Vp = -(-V // bv) * bv
    if (Rp, Vp) != (R, V):
        # NEG padding never wins the argmax
        logits = jnp.pad(logits, ((0, Rp - R), (0, Vp - V)),
                         constant_values=NEG)
        eps = jnp.pad(eps, ((0, Rp - R), (0, Vp - V)), constant_values=0.0)

    out = pl.pallas_call(
        functools.partial(_verify_kernel, bv=bv),
        grid=(Rp // br, Vp // bv),
        in_specs=[
            pl.BlockSpec((br, bv), lambda i, j: (i, j)),
            pl.BlockSpec((br, bv), lambda i, j: (i, j)),
        ],
        # rank-2 (br, 1) out/scratch blocks: Mosaic refuses rank-1 ones
        out_specs=pl.BlockSpec((br, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((br, 1), jnp.float32),   # running max
            pltpu.VMEM((br, 1), jnp.int32),     # running argmax
        ],
        interpret=interpret,
    )(logits, eps)
    return out[:R, 0]
