"""Operation and byte counts of the paged kernel and of the model step, at
shapes worked out by hand, and the dense GQA family's counts against the
formulas they were moved from."""
import pytest

from bench import model, roofline
from bench.tests.test_config_keys import MISTRAL

DENSE = model.family({"family": "dense_gqa"})
QWEN = dict(hidden_size=2048, num_attention_heads=16, num_key_value_heads=8,
            head_dim=128, intermediate_size=6144, num_hidden_layers=28,
            vocab_size=151936)
# H 4, KV 2, d 16, one layer
TINY = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_hidden_layers=1)


def test_paged_call_counts_by_hand():
    # one row of cached length 100, W = 8, H = 4, KV = 2, d = 16, bf16
    f, b = DENSE.paged_attn_call(TINY, [100], 8)
    assert f == 4 * 4 * 8 * 108 * 16
    assert b == (2 * 108 * 2 * 16 * 2      # K, V of 108 positions
                 + 2 * 8 * 4 * 16 * 2      # queries and outputs
                 + 2 * 8 * 2 * 16 * 2)     # new K, V rows
    assert DENSE.paged_attn_call(TINY, [100, 100], 8) == (2 * f, 2 * b)
    # one call a layer
    assert DENSE.paged_attn_call(dict(TINY, num_hidden_layers=3), [100],
                                 8) == (3 * f, 3 * b)


def test_prefill_counts_are_causal():
    # positions [2, 5): queries see 3, 4 and 5 keys
    f, b = DENSE.paged_attn_prefill(TINY, 2, 5)
    assert f == 4 * 4 * 16 * (3 + 4 + 5)
    assert b == 2 * 5 * 2 * 16 * 2 + 2 * 3 * 4 * 16 * 2 + 2 * 3 * 2 * 16 * 2
    assert DENSE.paged_attn_prefill(TINY, 5, 5) == (0, 0)


def test_least_time_picks_the_larger_bound():
    pk = roofline.peaks_for("TPU v5 lite")
    t, bound = roofline.least_time(197e12, 1.0, pk)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = roofline.least_time(1.0, 819e9, pk)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_qwen3_matmul_params_and_token_flops():
    per_layer = (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
                 + 3 * 2048 * 6144)
    n = 28 * per_layer + 151936 * 2048
    assert DENSE.matmul_params(QWEN) == n
    assert DENSE.matmul_params(model.load_config("qwen3-1.7b")) == n
    assert DENSE.token_flops(QWEN, 0) == 2 * n
    assert DENSE.token_flops(QWEN, 10) == 2 * n + 4 * 28 * 16 * 128 * 10
    assert DENSE.span_flops(QWEN, 3, 6) == sum(
        DENSE.token_flops(QWEN, p) for p in (3, 4, 5))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks_for("TPU v99")
    assert roofline.peaks_for("TPU v5 lite").bf16_flops == 197e12


# The counts as bench/roofline.py had them before they moved into the
# family: per layer, from loose GQA dims.

def formula_paged_attn_call(lengths, W, *, n_heads, n_kv_heads, head_dim,
                            kv_bytes=2, act_bytes=2):
    H, KV, d = n_heads, n_kv_heads, head_dim
    flops = bytes_ = 0
    for ell in lengths:
        vis = int(ell) + W
        flops += 4 * H * W * vis * d
        bytes_ += (2 * vis * KV * d * kv_bytes + 2 * W * H * d * act_bytes
                   + 2 * W * KV * d * kv_bytes)
    return flops, bytes_


def formula_paged_attn_prefill(start, end, *, n_heads, n_kv_heads, head_dim,
                               kv_bytes=2, act_bytes=2):
    H, KV, d = n_heads, n_kv_heads, head_dim
    n = end - start
    if n <= 0:
        return 0, 0
    keys = (start + 1 + end) * n // 2
    return (4 * H * d * keys,
            2 * end * KV * d * kv_bytes + 2 * n * H * d * act_bytes
            + 2 * n * KV * d * kv_bytes)


def formula_matmul_params(cfg):
    d, H, KV, hd, F = (cfg["hidden_size"], cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["intermediate_size"])
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def formula_span_flops(cfg, start, end):
    n = end - start
    if n <= 0:
        return 0
    pos_sum = (start + end - 1) * n // 2
    return (2 * formula_matmul_params(cfg) * n
            + 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * pos_sum)


LENGTHS = [0, 1, 15, 16, 511, 512, 6531, 8177, 9207]


@pytest.mark.parametrize("name", ["qwen3-1.7b", "mistral-large-123b"])
def test_counts_equal_the_moved_formulas(name):
    cfg = (model.load_config(name) if name == "qwen3-1.7b"
           else dict(MISTRAL))
    fam = model.family(cfg)
    L = cfg["num_hidden_layers"]
    dims = dict(n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"])
    assert fam.matmul_params(cfg) == formula_matmul_params(cfg)
    for W in (1, 2, 4, 8, 64):
        for i in range(len(LENGTHS)):
            rows = LENGTHS[i:] + LENGTHS[:i][:3]
            f, b = formula_paged_attn_call(rows, W, **dims)
            assert fam.paged_attn_call(cfg, rows, W) == (L * f, L * b)
    for start in LENGTHS:
        for n in (0, 1, 7, 64, 500):
            f, b = formula_paged_attn_prefill(start, start + n, **dims)
            assert fam.paged_attn_prefill(cfg, start, start + n) == (
                L * f, L * b)
            assert fam.span_flops(cfg, start, start + n) == \
                formula_span_flops(cfg, start, start + n)
