import json
import os

import pytest

from bench import flops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_mlp_count_is_six_per_weight_per_sample():
    assert flops.mlp_matmul_params((784, 200, 10)) == 158_800
    assert flops.mlp_train_flops_per_event((784, 200, 10), 128) == (
        6 * 158_800 * 128)


def test_kernel_bytes_of_an_f32_leaf_are_k_plus_8_passes():
    assert flops.apply_kernel_bytes([((784, 200), 4)], 128) == (
        136 * 156_800 * 4)


def test_kernel_bytes_of_a_bf16_leaf_count_f32_statistics():
    p = 2048 * 8512
    assert flops.apply_kernel_bytes([((2048, 8512), 2)], 4) == (
        6 * p * 2 + 6 * p * 4)


def test_mamba2_matmul_params_at_four_layers():
    c = config("mamba2-1.3b-l4")
    # in_proj 2048x8512 and out_proj 4096x2048 per layer, unembed 2048x50280
    assert flops.mamba2_matmul_params(c) == 4 * (2048 * 8512 + 4096 * 2048) \
        + 2048 * 50280 == 206_258_176
    per_token = flops.mamba2_train_flops_per_token(c)
    assert 6 * 206_258_176 < per_token < 1.05 * 6 * 206_258_176


def test_ssd_terms_at_published_widths():
    c = config("mamba2-1.3b-l4")
    Q, N, H, P = 128, 128, 64, 64
    assert flops.ssd_flops_per_token(c) == 2 * Q * N + 2 * Q * H * P \
        + 4 * N * H * P


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
