"""`flops.py` against a hand count at Mistral-7B-v0.3's widths."""

import pytest

from bench_matrix import flops, spec

D4 = spec.load("configs", "mistral-7b-v0.3-d4")
D16 = spec.load("configs", "mistral-7b-v0.3-d16")


def test_parameter_counts_by_hand():
    m = flops.matmul_params(D4)
    # q 4096x4096, k and v 4096x1024, o 4096x4096; three 4096x14336 matrices
    assert m["attn"] == 16777216 + 2 * 4194304 + 16777216 == 41943040
    assert m["mlp"] == 3 * 58720256 == 176160768
    assert m["head"] == 4096 * 32768 == 134217728
    assert flops.param_count(D4) == 4 * (218103808 + 8192) + 4096 + 2 * 134217728
    assert round(flops.param_count(D4) / 1e6) == 1141
    assert round(flops.param_count(D16) / 1e6) == 3758


def test_training_flops_per_token_by_hand():
    # 6 flops a matmul parameter; attention 2 matmuls x 2 flops x 4096 keys
    # x 4096 width, halved by the causal mask, tripled for the backward pass
    attn = 3 * 4 * 4096 * 4096 * 0.5
    want4 = 6 * (4 * 218103808 + 134217728) + 4 * attn
    assert flops.train_flops_per_token(D4, 4096) == pytest.approx(want4)
    assert want4 == pytest.approx(6.44e9, rel=1e-2)
    assert flops.train_flops_per_token(D16, 4096) == pytest.approx(2.335e10, rel=1e-2)


def test_flash_call_and_roofline():
    c = flops.flash_call(2, 4096, 32, 128)
    mm = 2 * 2 * 32 * 4096 * 4096 * 128 * 0.5
    assert c["forward_flops"] == 2 * mm and c["backward_flops"] == 5 * mm
    assert c["forward_bytes"] == 4 * 2 * 4096 * 32 * 128 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = flops.roofline_seconds(c["forward_flops"], c["forward_bytes"], peaks)
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(c["forward_flops"] / 197e12)
    assert flops.roofline_seconds(1.0, 1e9, peaks)["bound"] == "memory"
