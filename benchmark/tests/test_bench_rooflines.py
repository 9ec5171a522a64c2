"""The histogram kernels' ops and bytes against the hand numbers of
ISSUE 22's Motivation."""

import json
from pathlib import Path

import pytest

from benchmark.harness import device
from benchmark.harness.manifest import load_plugin, repo_root

roof = load_plugin(repo_root(), "rooflines", "hist_round")
V5E = device.peaks_for("TPU v5 lite")


def test_issued_flops_of_one_full_width_pass():
    # 2 * (48 * 3) * N * 28 * 255 per 1M rows = 2.06e12, ~10 ms at 197 TF/s
    flops = roof.issued_flops(1_000_000, 28, 255, 48)
    assert flops == 2 * 48 * 3 * 1_000_000 * 28 * 255
    assert flops == pytest.approx(2.06e12, rel=0.005)
    assert flops / V5E["bf16_flops"] == pytest.approx(10.4e-3, rel=0.01)


def test_needed_bytes_bound_the_floor():
    # N * (28 * 4 + 12) bytes = 1.6 ms per 10.5M rows at 819 GB/s
    assert roof.needed_bytes(10_500_000, 28) == 10_500_000 * 124
    floor, bound = roof.floor_seconds(10_500_000, 28, V5E, "int16")
    assert bound == "hbm"
    assert floor == pytest.approx(1.59e-3, rel=0.01)
    assert roof.needed_adds(10_500_000, 28) / V5E["bf16_flops"] < 1e-5


def test_peak_follows_the_operand_type():
    assert roof.peak_ops(V5E, "int16") == 197e12
    assert roof.peak_ops(V5E, "int8") == 393e12


def test_peaks_table_source_and_unknown_kind():
    table = json.loads(
        Path(device.__file__).with_name("peaks.json").read_text())
    assert "TPU v5e" in table["_source"]
    assert V5E["hbm_bytes_per_s"] == 819e9 and V5E["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        device.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        device.peaks_for("_source")


@pytest.mark.parametrize("instruction, slots", [
    ("%hist_round_tpu.8 = (f32[144,7140]{1,0:T(8,128)S(1)}, "
     "s32[1,10500096]{1,0:T(1,128)}) custom-call(s32[48,16]{1,0} %c)", 48),
    ("%hist_round_tpu.2 = (s32[96,7140]{1,0}, s32[1,2625024]{1,0}) "
     "custom-call(s32[28,2625024] %a, f32[8,2625024] %b)", 32),
    ("%hist_nat_tpu.10 = f32[3,7140]{1,0:T(4,128)S(1)} custom-call(", 1),
])
def test_slots_read_from_the_call_shapes(instruction, slots):
    import re

    assert re.search(roof.KERNEL_PATTERN, instruction)
    assert roof.slots_of(instruction, 28, 255) == slots


def test_slots_absent_when_no_shape_fits():
    assert roof.slots_of("%fusion.1 = f32[8,1024]{1,0} fusion(", 28, 255) \
        is None
    import re

    assert not re.search(roof.KERNEL_PATTERN, "%take_small_tpu.2 = f32[1,8]")
