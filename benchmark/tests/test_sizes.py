"""The ops-and-bytes and model-FLOPs functions against numbers worked by
hand, and the peaks table."""
import pytest

from benchmark.harness import readers, spec

LM = spec.load_json(spec.BENCH_DIR + "/configs/nope-lm-2048x24.json")
RN = spec.load_json(spec.BENCH_DIR + "/configs/resnet50.json")
nope_lm = spec.load_module("reference", "nope_lm")
resnet50 = spec.load_module("reference", "resnet50")


def test_lm_has_1_414_billion_parameters():
    layer = 4 * (2048 * 2048 + 2048) + 2 * 2048 * 8192 + 8192 + 2048 + 4 * 2048
    assert layer == 50_358_272
    want = 24 * layer + 2 * 50272 * 2048 + 50272 + 2 * 2048
    assert nope_lm.param_count(LM) == want == LM["parameters"] == 1_414_567_008


def test_one_kv_block_is_3_mib_in_bf16():
    assert nope_lm.kv_bytes_per_token(LM, 2) * 16 == 3 * 2**20


def test_resnet50_is_4_09_gmac_and_25_6_m_parameters():
    assert resnet50.forward_macs_per_image() / 1e9 == pytest.approx(4.09, abs=0.01)
    assert resnet50.param_count() == RN["parameters"] == 25_557_032
    assert resnet50.train_flops_per_image() / 1e9 == pytest.approx(24.5, abs=0.1)
    # the program's max pool rounds up: 57/29/15/8 feature maps, 13 % more
    executed = resnet50.forward_macs_per_image(as_executed=True)
    assert executed / resnet50.forward_macs_per_image() == pytest.approx(1.13, abs=0.005)


def test_flash_decode_cost_is_the_live_kv_once():
    cost = readers.kernel_cost("mxtpu_flash_decode")
    c = cost(cached_tokens=1000, rows=4, heads=32, head_dim=64, kv_itemsize=2)
    assert c["bytes"] == 2 * 1000 * 2048 * 2 + 2 * 4 * 2048 * 2
    assert c["flops"] == 4 * 1000 * 2048
    assert cost(1000, 4, 32, 64, 2, layers=24)["bytes"] == 24 * c["bytes"]


def test_roofline_takes_the_larger_bound():
    peaks = spec.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    mem = readers.roofline_pct({"bytes": 819e9, "flops": 1.0}, 2.0, peaks)
    assert mem == pytest.approx(50.0)
    cmp_ = readers.roofline_pct({"bytes": 1.0, "flops": 197e12}, 4.0, peaks)
    assert cmp_ == pytest.approx(25.0)


def test_unknown_device_kind_raises():
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v9 imaginary")
