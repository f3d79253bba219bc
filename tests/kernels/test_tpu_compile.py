"""Compile the main-path Pallas kernels for a TPU v5e at published widths.

Interpret-mode tests (the rest of tests/kernels) check the kernels' numbers
but not what Mosaic accepts: block tiling, VMEM/SMEM use, ranks. These
tests compile each kernel with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology — no chip is needed, nothing runs — and
check that the program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and pytest-xdist
workers import every test file. Keep all such tests in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention.kernel import decode_attention_kernel
from repro.kernels.paged_attention.kernel import (pages_per_block,
                                                  paged_decode_kernel,
                                                  paged_latent_kernel,
                                                  paged_write_kernel)
from repro.kernels.spec_verify.kernel import spec_verify_kernel

# the serving engine's shapes: batch 8, window 8, max_len 1024, block 16
B, W, MAX_LEN, BS = 8, 8, 1024, 16
NB = -(-(MAX_LEN + W) // BS)                  # block-table width
P = 1 + B * NB + 2 * NB                       # pool blocks (engine default)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def s(topo):
    """``s(shape, dtype=bf16)``: an argument shape on one described chip."""
    return functools.partial(_shape, SingleDeviceSharding(topo.devices[0]))


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _compile_paged(s, *, B, W, nb, P, KV, G, d, window=0):
    ppb = pages_per_block(nb=nb, bs=BS, KV=KV, widths=(d, d), R=G * W, dv=d,
                          W=W, itemsize=2)
    return _compile_text(
        lambda *a: paged_decode_kernel(*a, W=W, ppb=ppb, window=window,
                                       interpret=False),
        s((B, KV, G * W, d)), s((P, BS, KV, d)), s((P, BS, KV, d)),
        s((B, W, KV, d)), s((B, W, KV, d)), s((B, nb), jnp.int32),
        s((B,), jnp.int32))


def test_paged_decode_kernel_compiles_at_qwen3_widths(s):
    cfg = get_config("qwen3-1.7b")
    text = _compile_paged(s, B=B, W=W, nb=NB, P=P, KV=cfg.n_kv_heads,
                          G=cfg.n_heads // cfg.n_kv_heads, d=cfg.head_dim)
    assert "tpu_custom_call" in text


# the qwen3-1.7b.docqa-8k cell's engine: batch 16, max_len 9216, block 16,
# 3,440 pool blocks; its rounds (W 2-8) and its 64-token prefill chunks
@pytest.mark.parametrize("batch,window", [(16, 2), (16, 4), (16, 8), (1, 64)])
def test_paged_decode_kernel_compiles_at_docqa_cell_shapes(s, batch, window):
    cfg = get_config("qwen3-1.7b")
    text = _compile_paged(s, B=batch, W=window, nb=-(-(9216 + 8) // BS),
                          P=3440, KV=cfg.n_kv_heads,
                          G=cfg.n_heads // cfg.n_kv_heads, d=cfg.head_dim)
    assert "tpu_custom_call" in text


# one kv head (gemma3's global and sliding-window local layers) in rounds
# and in 64-token prefill chunks, where the most pages per block meet the
# most query rows
@pytest.mark.parametrize("batch,window", [(B, W), (1, 64)])
@pytest.mark.parametrize("sliding", [False, True], ids=["global", "local"])
def test_paged_decode_kernel_compiles_with_one_kv_head(s, batch, window,
                                                       sliding):
    cfg = get_config("gemma3-1b")
    text = _compile_paged(s, B=batch, W=window, nb=-(-(9216 + window) // BS),
                          P=3440, KV=cfg.n_kv_heads,
                          G=cfg.n_heads // cfg.n_kv_heads, d=cfg.head_dim,
                          window=cfg.sliding_window if sliding else 0)
    assert "tpu_custom_call" in text


# heads narrower than a 128-lane tile (internvl2-1b, head_dim 64)
@pytest.mark.parametrize("batch,window", [(B, W), (1, 64)])
def test_paged_decode_kernel_compiles_with_narrow_heads(s, batch, window):
    cfg = get_config("internvl2-1b")
    text = _compile_paged(s, B=batch, W=window, nb=NB, P=P,
                          KV=cfg.n_kv_heads, G=cfg.n_heads // cfg.n_kv_heads,
                          d=cfg.head_dim)
    assert "tpu_custom_call" in text


def test_paged_latent_kernel_compiles_at_mla_widths(s):
    cfg = get_config("deepseek-v3-671b")
    H, r, dr = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_dim
    ppb = pages_per_block(nb=NB, bs=BS, KV=1, widths=(r, dr), R=H * W, dv=r,
                          W=W, itemsize=2)
    text = _compile_text(
        lambda *a: paged_latent_kernel(*a, W=W, ppb=ppb, scale=0.1,
                                       interpret=False),
        s((B, 1, H * W, r)), s((B, 1, H * W, dr)), s((P, BS, 1, r)),
        s((P, BS, 1, dr)), s((B, W, 1, r)), s((B, W, 1, dr)),
        s((B, NB), jnp.int32), s((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("trail", [(8, 128), (512,), (64,)],
                         ids=["kv-heads", "mla-latent", "mla-rope"])
def test_paged_write_kernel_compiles(s, trail):
    text = _compile_text(
        lambda *a: paged_write_kernel(*a, interpret=False),
        s((P, BS) + trail), s((B, W) + trail), s((B, NB), jnp.int32),
        s((B,), jnp.int32), s((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_spec_verify_kernel_compiles_at_qwen3_vocab(s):
    V = get_config("qwen3-1.7b").vocab
    text = _compile_text(
        lambda a, b: spec_verify_kernel(a, b, interpret=False),
        s((B * W, V), jnp.float32), s((B * W, V), jnp.float32))
    assert "tpu_custom_call" in text


def test_decode_attention_kernel_compiles_at_qwen3_widths(s):
    cfg = get_config("qwen3-1.7b")
    BH, d = B * cfg.n_heads, cfg.head_dim
    text = _compile_text(
        lambda *a: decode_attention_kernel(*a, interpret=False),
        s((BH, W, d)), s((BH, MAX_LEN, d)), s((BH, MAX_LEN, d)),
        s((BH,), jnp.int32))
    assert "tpu_custom_call" in text
