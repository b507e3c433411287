"""The cost arithmetic against hand-worked numbers for both encoders."""

import pytest

from chipbench import costs

MINILM = {"hidden": 384, "mlp_dim": 1536, "layers": 6}
E5 = {"hidden": 1024, "mlp_dim": 4096, "layers": 24}


def test_encoder_flops_minilm_74_tokens():
    # per token and layer: 2*(4*384^2 + 2*384*1536) + 4*74*384
    #   = 2*1,769,472 + 113,664 = 3,652,608; six layers; 74 tokens
    assert costs.encoder_flops(MINILM, 74) == 74 * 6 * 3_652_608 == 1_621_757_952


def test_encoder_flops_e5_352_tokens():
    # 2*(4*1024^2 + 2*1024*4096) + 4*352*1024 = 25,165,824 + 1,441,792
    assert costs.encoder_flops(E5, 352) == 352 * 24 * 26_607_616 == 224_781_139_968


def test_weight_and_activation_bytes():
    # per layer 4h^2 + 2h*ffn + 9h + ffn parameters, bf16
    assert costs.encoder_weight_bytes(MINILM) == 2 * 6 * 1_774_464
    assert costs.encoder_weight_bytes(E5) == 2 * 24 * 12_596_224 == 604_618_752
    assert costs.encoder_activation_bytes(E5, 352) == 2 * 2 * 352 * 1024 * 24


def test_roofline_picks_the_binding_side():
    # 197 TFLOP of work against 1 byte: one second, compute-bound
    r = costs.roofline_seconds(197e12, 1.0, "TPU v5 lite")
    assert r == {"seconds": 1.0, "bound": "compute"}
    r = costs.roofline_seconds(1.0, 819e9, "TPU v5 lite")
    assert r == {"seconds": 1.0, "bound": "memory"}


def test_unknown_device_is_an_error():
    assert costs.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(costs.UnknownDevice):
        costs.peaks("cpu")
