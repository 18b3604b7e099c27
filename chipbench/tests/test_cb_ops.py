"""Operations and bytes from shapes, against hand counts at minitron-4b's
published widths."""

import json

import chipbench_fixtures as fx
import pytest

from harness import ops
from harness.peaks import peaks

CFG = json.loads((fx.CB / "configs" / "minitron4b.json").read_text())
D, F, L, V = 3072, 9216, 32, 256000
Q, KV = 24 * 128, 8 * 128


def test_parameters_by_hand():
    per_layer = D * Q + 2 * D * KV + Q * D + 2 * D * F      # 81,788,928
    assert ops.layer_matmul_params(CFG) == per_layer == 81_788_928
    assert ops.layer_params(CFG) == per_layer + 2 * D       # 81.8M
    total = L * (per_layer + 2 * D) + 2 * V * D + D
    assert ops.total_params(CFG) == total == 4_190_309_376   # 4.19B


def test_served_bytes_by_hand():
    # every bf16 weight but the embedding table: 6.81 GB read per decode step
    bf16 = 2 * (L * (81_788_928 + 2 * D) + D * V + D)
    assert ops.served_weight_bytes(CFG, "bfloat16") == bf16 == 6_807_754_752
    # the whole checkpoint: what the deploy holds on the device
    assert ops.served_weight_bytes(CFG, "bfloat16", True) == 8_380_618_752
    # int8 matrices, one f32 scale per output column, bf16 norms and head
    cols = Q + 2 * KV + D + F + D
    i8 = L * (81_788_928 + 4 * cols + 4 * D) + 2 * D * V + 2 * D
    assert ops.served_weight_bytes(CFG, "int8") == i8


def test_decode_step_by_hand():
    # two slots holding 99 and 9 positions: each attends over one more
    lengths = [99, 9]
    flops = 2 * 2 * (L * 81_788_928 + D * V) + 4 * L * Q * (100 + 10)
    assert ops.decode_flops(CFG, lengths) == flops
    kv = 2 * L * KV * 2                                   # k and v, bf16
    assert ops.kv_bytes_per_position(CFG) == kv == 131_072
    assert ops.decode_bytes(CFG, lengths, "bfloat16") == \
        6_807_754_752 + 110 * kv


def test_prefill_by_hand():
    S = 2048
    flops = 2 * L * 81_788_928 * S + 4 * L * Q * S * (S + 1) / 2 + 2 * D * V
    assert ops.prefill_flops(CFG, S) == flops
    assert flops / 1e12 == pytest.approx(11.55, abs=0.01)


def test_int8_matmul_by_hand():
    calls = ops.int8_calls(CFG, 8)
    assert len(calls) == 6 * L
    assert calls[4] == (8, D, F)
    assert ops.int8_matmul_ops(8, D, F) == 2 * 8 * D * F
    assert ops.int8_matmul_bytes(8, D, F) == 8 * D * 2 + D * F + 4 * F + \
        8 * F * 2
    pk = peaks("TPU v5 lite")
    # M = 8 is bound by bytes, M = 2048 by operations
    t8 = ops.roofline_s(ops.int8_matmul_ops(8, D, F),
                        ops.int8_matmul_bytes(8, D, F),
                        pk["bf16_flops"], pk["hbm_bytes_per_s"])
    assert t8 == ops.int8_matmul_bytes(8, D, F) / 819e9
    t2k = ops.roofline_s(ops.int8_matmul_ops(2048, D, F),
                         ops.int8_matmul_bytes(2048, D, F),
                         pk["bf16_flops"], pk["hbm_bytes_per_s"])
    assert t2k == ops.int8_matmul_ops(2048, D, F) / 197e12


def test_peaks_table_refuses_an_unknown_chip():
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9000")
