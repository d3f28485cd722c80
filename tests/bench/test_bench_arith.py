"""Operations and bytes from shapes, against hand-reckoned values."""
import pytest

from bench_testkit import SMALL_MODEL, load_cell
from benchlib import arith

# per token: the layer matrices times the layers; per sampled row: d x V
# unimo-text: (4*1024*1024 + 2*1024*4096) * 24 = 301,989,888;
#           1024 * 12800 = 13,107,200
# unimo-text, the tests' small size: (4*256*256 + 2*256*512) * 2
#           = 1,048,576; 256 * 1600 = 409,600
PER_TOKEN = {"unimo-text": 301_989_888, "unimo-text-small": 1_048_576}
PER_ROW = {"unimo-text": 13_107_200, "unimo-text-small": 409_600}


def dims(config):
    cell = load_cell("unimo-text.offline-batch")
    c = cell.config
    if config.endswith("-small"):
        c = {**c, **SMALL_MODEL["unimo-text"]}
    return cell.model.dims(c)


@pytest.mark.parametrize("config", sorted(PER_TOKEN))
def test_matmul_flops(config):
    m = dims(config)
    assert arith.matmul_flops(m, 1, 0) == 2 * PER_TOKEN[config]
    assert arith.matmul_flops(m, 0, 1) == 2 * PER_ROW[config]
    assert arith.matmul_flops(m, 512, 32) == \
        2 * (512 * PER_TOKEN[config] + 32 * PER_ROW[config])


@pytest.mark.parametrize("config", sorted(PER_TOKEN))
def test_quant_matmul_calls_cover_every_weight(config):
    m = dims(config)
    calls = arith.quant_matmul_calls(m, 128, 32)
    assert len(calls) == m["L"] * (4 + m["ffn_mats"]) + 1
    assert sum(M * K * N for M, K, N in calls) == \
        128 * PER_TOKEN[config] + 32 * PER_ROW[config]


def test_quant_matmul_cost_and_roofline_on_v5e():
    flops, nbytes = arith.quant_matmul_cost(128, 1024, 4096)
    assert flops == 1_073_741_824                  # 2 * 128 * 1024 * 4096
    # f32 x 128x1024, int8 codes 1024x4096, f32 scales, f32 out 128x4096
    assert nbytes == 524_288 + 4_194_304 + 16_384 + 2_097_152
    peak = arith.peaks("TPU v5 lite")
    assert arith.roofline_seconds(flops, nbytes, peak) == \
        pytest.approx(6_832_128 / 819e9)           # bound by the bytes
    assert arith.roofline_seconds(2 * 4096 ** 3, 0, peak) == \
        pytest.approx(2 * 4096 ** 3 / 197e12)      # bound by the ops


def test_a_chip_kind_without_peaks_is_an_error():
    with pytest.raises(KeyError):
        arith.peaks("TPU v9 imaginary")
