"""Operation and byte counts of the paged kernel and of the model step, at
shapes worked out by hand."""
import pytest

from bench import roofline

QWEN = dict(hidden_size=2048, num_attention_heads=16, num_key_value_heads=8,
            head_dim=128, intermediate_size=6144, num_hidden_layers=28,
            vocab_size=151936)


def test_paged_call_counts_by_hand():
    # one row of cached length 100, W = 8, H = 4, KV = 2, d = 16, bf16
    f, b = roofline.paged_attn_call([100], 8, n_heads=4, n_kv_heads=2,
                                    head_dim=16)
    assert f == 4 * 4 * 8 * 108 * 16
    assert b == (2 * 108 * 2 * 16 * 2      # K, V of 108 positions
                 + 2 * 8 * 4 * 16 * 2      # queries and outputs
                 + 2 * 8 * 2 * 16 * 2)     # new K, V rows
    f2, b2 = roofline.paged_attn_call([100, 100], 8, n_heads=4,
                                      n_kv_heads=2, head_dim=16)
    assert (f2, b2) == (2 * f, 2 * b)


def test_prefill_counts_are_causal():
    # positions [2, 5): queries see 3, 4 and 5 keys
    f, b = roofline.paged_attn_prefill(2, 5, n_heads=4, n_kv_heads=2,
                                       head_dim=16)
    assert f == 4 * 4 * 16 * (3 + 4 + 5)
    assert b == 2 * 5 * 2 * 16 * 2 + 2 * 3 * 4 * 16 * 2 + 2 * 3 * 2 * 16 * 2
    assert roofline.paged_attn_prefill(5, 5, n_heads=4, n_kv_heads=2,
                                       head_dim=16) == (0, 0)


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
    assert roofline.matmul_params(QWEN) == n
    assert roofline.token_flops(QWEN, 0) == 2 * n
    assert roofline.token_flops(QWEN, 10) == 2 * n + 4 * 28 * 16 * 128 * 10
    assert roofline.span_flops(QWEN, 3, 6) == sum(
        roofline.token_flops(QWEN, p) for p in (3, 4, 5))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks_for("TPU v99")
    assert roofline.peaks_for("TPU v5 lite").bf16_flops == 197e12
