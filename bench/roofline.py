"""Peak rates of each chip and the operations and bytes the served path needs.

The peaks are keyed by ``device_kind`` as JAX reports it. A kind that is not
in the table is an error: a number divided by a guessed peak is no number.

Source of the TPU v5e row: Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect per chip.

The counts below are of the work a call *needs*, from its shapes and the
lengths it served, never from what an implementation happens to do: padding,
tiles past a row's length and rejected window positions add time but no
count, so a faster implementation of the same work raises the share.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s, dense bf16 matmul
    int8_ops: float          # OP/s
    hbm_bytes: float         # bytes/s
    hbm_capacity: float      # bytes
    ici_bytes: float         # bytes/s per chip, all links
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=819e9,
        hbm_capacity=16 * 2 ** 30, ici_bytes=1600e9 / 8,
        source='Google Cloud documentation, "TPU v5e"'),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


# ---------------------------------------------------------------------------
# paged attention kernel
# ---------------------------------------------------------------------------

def paged_attn_call(lengths, W: int, *, n_heads: int, n_kv_heads: int,
                    head_dim: int, kv_bytes: int = 2, act_bytes: int = 2):
    """(flops, bytes) one layer's paged attention call needs for the rows
    in ``lengths`` (each row's cached length before the window; inactive rows
    are left out by the caller). Per row: K and V of the ``length + W``
    visible positions read once, the W queries read and the W outputs
    written, the W new K and V rows written; ``4 H W (length + W) d``
    FLOPs (QK^T and PV)."""
    H, KV, d = n_heads, n_kv_heads, head_dim
    flops = bytes_ = 0
    for ell in lengths:
        vis = int(ell) + W
        flops += 4 * H * W * vis * d
        bytes_ += (2 * vis * KV * d * kv_bytes           # K, V read
                   + 2 * W * H * d * act_bytes           # queries + output
                   + 2 * W * KV * d * kv_bytes)          # new K, V written
    return flops, bytes_


def paged_attn_prefill(start: int, end: int, *, n_heads: int,
                       n_kv_heads: int, head_dim: int, kv_bytes: int = 2,
                       act_bytes: int = 2):
    """(flops, bytes) one layer needs to prefill positions ``[start, end)``
    through the paged cache, however the engine splits them into calls:
    K and V of positions ``< end`` read once, queries and outputs of the new
    positions, their K and V written; causal ``4 H d (q + 1)`` FLOPs per
    query position ``q``."""
    H, KV, d = n_heads, n_kv_heads, head_dim
    n = end - start
    if n <= 0:
        return 0, 0
    keys = (start + 1 + end) * n // 2                   # sum of (q + 1)
    flops = 4 * H * d * keys
    bytes_ = (2 * end * KV * d * kv_bytes + 2 * n * H * d * act_bytes
              + 2 * n * KV * d * kv_bytes)
    return flops, bytes_


def least_time(flops: float, bytes_: float, peaks: Peaks):
    """(seconds, bound) of the roofline: the larger of compute and memory
    time, and which of the two it is."""
    tc, tm = flops / peaks.bf16_flops, bytes_ / peaks.hbm_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")


# ---------------------------------------------------------------------------
# whole forward pass
# ---------------------------------------------------------------------------

def matmul_params(cfg) -> int:
    """Matmul parameters a token passes through: every layer's attention
    projections and MLP, plus the output head (tied or not). The embedding
    lookup is a gather, not a matmul."""
    d, H, KV, hd, F = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def token_flops(cfg, position: int) -> int:
    """Forward FLOPs one token at ``position`` needs: ``2 x`` matmul
    parameters plus ``4 L H d position`` of attention."""
    return (2 * matmul_params(cfg)
            + 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * int(position))


def span_flops(cfg, start: int, end: int) -> int:
    """``token_flops`` summed over positions ``[start, end)``."""
    n = end - start
    if n <= 0:
        return 0
    pos_sum = (start + end - 1) * n // 2
    return (2 * matmul_params(cfg) * n
            + 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * pos_sum)
