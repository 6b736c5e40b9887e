"""The dense family's operation and byte counts against counts worked by hand
from the published shapes, and the table of peaks."""
import pytest

from bench import common
from bench.reference import dense
from bench.run import ROOT

QWEN = common.load_json(ROOT, "bench", "configs", "qwen2-1.5b.json")
SMOL = common.load_json(ROOT, "bench", "configs", "smollm-360m.json")

# qwen2-1.5b, one layer's projections:
#   q, k, v: 1536 x (1536 + 2 x 256) = 3,145,728; o: 1536 x 1536 = 2,359,296;
#   gate, up, down: 3 x 1536 x 8960 = 41,287,680; together 46,792,704.
# 28 layers + the output head 1536 x 151,936 = 233,373,696:
#   1,543,569,408 parameters in products.
# Read once per decode step: add per layer two norms (3,072) and the
#   q/k/v biases (2,048), and the final norm (1,536): 1,543,714,304.
QWEN_LAYER, QWEN_MM, QWEN_READ = 46_792_704, 1_543_569_408, 1_543_714_304
# smollm-360m: 960 x (960 + 640) + 960 x 960 + 3 x 960 x 2560 = 9,830,400
# per layer; 32 layers + head 960 x 49,152 = 361,758,720.
SMOL_LAYER, SMOL_MM = 9_830_400, 361_758_720


def test_parameter_counts():
    q, s = dense.Dense.from_config(QWEN), dense.Dense.from_config(SMOL)
    assert dense.layer_matmul_params(q) == QWEN_LAYER
    assert dense.matmul_params(q) == QWEN_MM
    assert dense.weight_params(q) == QWEN_READ
    assert dense.layer_matmul_params(s) == SMOL_LAYER
    assert dense.matmul_params(s) == SMOL_MM
    # whole model in bf16 with the embedding: 3.554 GB, as the program's
    # compiled decode step reports
    assert (QWEN_READ + 151_936 * 1536) * 2 == 3_554_176_000


def test_decode_bytes():
    # one sequence at context 100: weights 2 x 1,543,714,304; one
    # embedding row 1536 x 2; K and V 28 x 2 x 2 x 128 x 2 = 28,672 B per
    # position for 100 read + 1 written; f32 logits 151,936 x 4
    want = (2 * QWEN_READ + 3_072 + 28_672 * 101 + 607_744)
    assert want == 3_090_935_296
    assert dense.decode_bytes(QWEN, 1, 100) == want


def test_decode_flops():
    # 2 x 1,543,569,408 + 28 layers x 4 x 12 heads x 128 x 100 positions
    assert dense.decode_flops(QWEN, 1, 100) == 3_087_138_816 + 17_203_200


def test_prefill_flops():
    # 128 tokens: 2 x 128 x 28 x 46,792,704 = 335,410,102,272; head at the
    # last position 2 x 1536 x 151,936 = 466,747,392; causal attention
    # 28 x 4 x 12 x 128 = 172,032 per query-key pair x 128 x 129 / 2
    assert dense.prefill_flops(QWEN, 128) == (
        335_410_102_272 + 466_747_392 + 172_032 * 8_256)


def test_train_flops_per_token():
    # 6 x parameters in products, plus 3 x attention over 2049 / 2
    # positions: smollm 3 x 32 x 4 x 15 x 64 = 368,640 per position
    assert dense.train_flops_per_token(SMOL, 2048) == (
        6 * SMOL_MM + 368_640 * 2049 // 2) == 2_548_224_000
    # qwen2: 3 x 28 x 4 x 12 x 128 = 516,096 per position
    assert dense.train_flops_per_token(QWEN, 2048) == (
        6 * QWEN_MM + 516_096 * 2049 // 2) == 9_790_156_800


def test_peaks_are_keyed_by_device_kind():
    pk = common.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks("cpu")
    with pytest.raises(KeyError):
        common.peaks("TPU v4")
