"""bench/counts.py against counts made by hand at small shapes."""
import pytest

from bench import counts

TINY = {"d_model": 8, "n_heads": 2, "n_kv_heads": 1, "head_dim": 4,
        "d_ff": 16, "vocab_size": 32, "n_layers": 2, "activation": "swiglu"}


def test_matmul_params_per_layer():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, three 8x16 FFN matrices
    assert counts.matmul_params_per_layer(TINY) == 64 + 32 + 32 + 64 + 384
    gelu = dict(TINY, activation="gelu")
    assert counts.matmul_params_per_layer(gelu) == 64 + 32 + 32 + 64 + 256


def test_param_count():
    # 2 layers of (576 + two gains of 8), final gain, embed and head 32x8
    assert counts.param_count(TINY) == 2 * (576 + 16) + 8 + 2 * 256


def test_forward_flops_by_hand():
    B, S = 3, 5
    dense = 2 * B * S * 576 * 2
    head = 2 * B * S * 8 * 32
    # causal: query i sees i + 1 keys, mean (S + 1) / 2; scores and the
    # weighted sum are 2 * dh each per key per head
    attn = 2 * 2 * B * sum(i + 1 for i in range(S)) * 2 * 4 * 2
    assert counts.forward_flops(TINY, B, S) == pytest.approx(dense + head + attn)
    assert counts.zo_step_flops(TINY, B, S, 1) == pytest.approx(
        2 * (dense + head + attn))


def test_zo_pass_bytes_by_hand():
    c = counts.zo_pass_cost(TINY, q=1, rank=4)
    # leaves: embed 32x8, head 8x32, final gain 8 (dense), ln1/ln2 as
    # [2, 8] (dense: 2 < 8), and per layer wq 8x8, wk/wv 8x4 (dense: 4 < 8),
    # wo 8x8, three 8x16
    elems = 256 + 256 + 8 + 16 + 16 + 2 * (64 + 32 + 32 + 64 + 3 * 128)
    factors = (32 + 8) * 4 * 2 + 2 * (2 * (8 + 8) * 4 + 3 * (8 + 16) * 4)
    assert c["passes"] == 3
    assert c["bytes"] == 3 * (4 * elems + 4 * factors)


def test_least_seconds_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(1000.0, 10.0, peak) == 10.0
    assert counts.least_seconds(10.0, 1000.0, peak) == 100.0


def test_decode_counts_by_hand():
    # one token over 10 cached positions: 2 layers x 2 heads x dh 4
    f, b = counts.decode_attention_cost(TINY, 10)
    assert f == 4 * 2 * 4 * 10 * 2
    assert b == 2 * 1 * 4 * 2 * 10 * 2  # k and v, 1 KV head, bf16
    assert counts.decode_flops(TINY, 10) == 2 * 576 * 2 + 2 * 8 * 32 + f
