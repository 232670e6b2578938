import pytest

from bench import flops as F
from bench.families import qwen as Q
from bench.model_config import load_config_file

C = load_config_file("qwen3-0.6b")
C25 = load_config_file("qwen2.5-0.5b")


def test_qwen3_hand_counts():
    # q 1024x16x128, k and v 1024x8x128, o 16x128x1024, gated MLP 3x1024x3072
    per = 2097152 + 2 * 1048576 + 2097152 + 3 * 3145728
    assert Q.layer_matmul_params(C) == per == 15728640
    # + 2 RMSNorm scales (1024) + q/k norm scales (128) per layer,
    # tied 151936 x 1024 embedding, final norm
    n = 151936 * 1024 + 28 * (per + 2048 + 256) + 1024
    assert Q.param_count(C) == n == 596049920
    assert Q.param_bytes(C) == 2 * n
    # 28 layers x K,V x 8 heads x 128 x bf16
    assert Q.kv_bytes_per_token(C) == 114688


def test_qwen25_hand_counts():
    # q 896x14x64, k and v 896x2x64, o 14x64x896, gated MLP 3x896x4864
    per = 802816 + 2 * 114688 + 802816 + 3 * 4358144
    assert Q.layer_matmul_params(C25) == per == 14909440
    # + 2 RMSNorm scales (896) + q, k, v biases (14 + 2 + 2 heads of 64)
    # per layer, tied 151936 x 896 embedding, final norm
    n = 151936 * 896 + 24 * (per + 1792 + 1152) + 896
    assert Q.param_count(C25) == n == 494032768
    assert Q.param_bytes(C25) == 2 * n
    # 24 layers x K,V x 2 heads x 64 x bf16
    assert Q.kv_bytes_per_token(C25) == 12288
    one = 2 * 24 * 14909440 + 4 * 24 * 14 * 64 * 1 + 2 * 896 * 151936
    assert Q.forward_flops(C25, 1, 1) == one


def test_forward_and_update_flops():
    one = 2 * 28 * 15728640 + 4 * 28 * 16 * 128 * 1 + 2 * 1024 * 151936
    assert Q.forward_flops(C, 1, 1) == one
    # a row of 3 tokens reads 1 + 2 + 3 keys; training is 3 forwards
    three = 3 * (2 * 28 * 15728640 + 2 * 1024 * 151936) + 4 * 28 * 2048 * 6
    assert Q.forward_flops(C, 3, F.causal_ctx(3)) == three
    assert F.update_flops(C, [(1, 2)]) == 3 * three


def test_decode_step_is_bandwidth_bound():
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = [300] * 16
    t = Q.decode_step_least(C, ctx, peak)
    assert t == pytest.approx((2 * 596049920 + 114688 * 4800) / 819e9)


def test_readers_get_the_family_counts():
    fl = F.counts(C)
    assert fl.forward_flops is Q.forward_flops
    assert fl.decode_step_least is Q.decode_step_least
    w = {"decode_tokens": 5, "decode_ctx": 700, "prefill_tokens": 30,
         "prefill_ctx": 465, "update_rows": [(30, 2), (20, 9)]}
    assert fl.window_flops(C, w) == (
        Q.forward_flops(C, 5, 700) + Q.forward_flops(C, 30, 465)
        + fl.update_flops(C, [(30, 2), (20, 9)]))
