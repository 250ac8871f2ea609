"""The trace reduction: interval arithmetic, op and program names, and the
whole reduction run against a small trace recorded on the chip and
checked in (``bench/testdata/record.py``: one engine serving two requests,
2 blocks at granite-8b's widths)."""
import gzip
import os
import shutil

import pytest

from benchutil import ROOT

from bench import trace_reduce as R

SMALL_TRACE = os.path.join(ROOT, "bench", "testdata", "serve.xplane.pb.gz")


def unpacked(tmp_dir) -> str:
    """The recorded trace, unpacked where ProfileData can read it."""
    path = os.path.join(str(tmp_dir), "serve.xplane.pb")
    with gzip.open(SMALL_TRACE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def test_union_and_clip():
    assert R.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert R.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert R.clip([(0, 1)], 2, 6) == []


@pytest.mark.parametrize("name,want", [
    ("%dequant_matmul.36 = f32[16,14336]{1,0} custom-call(f32[16,2048]{1,0}"
     " %a)", "dequant_matmul"),
    ("%flash_decode.8 = f32[12,32,128]{2,1,0} custom-call()", "flash_decode"),
    ("%while.5 = (s32[]{:T(128)}, f32[12,1,4096])", "while"),
    ("%bitcast_dynamic-update-slice_fusion.5 = f32[36,1024]",
     "bitcast_dynamic-update-slice_fusion"),
    ("awp_pgd.3", "awp_pgd"), ("copy", "copy"),
])
def test_op_base_names(name, want):
    assert R.base_name(name) == want


def test_shapes_from_hlo_text():
    ev = ("%dequant_matmul.36 = f32[16,14336]{1,0:T(8,128)S(1)} custom-call("
          "f32[16,2048]{1,0:T(8,128)} %x, u8[14336,2048]{1,0} %p, "
          "s32[] %n)")
    assert R.shapes(ev) == ((16, 14336), (16, 2048), (14336, 2048), ())


def test_program_names():
    assert R.module_name("jit_decode_fn(3856446361299472740)") == "decode_fn"
    assert R.module_name("jit_prefill_fn") == "prefill_fn"


def test_gaps_are_named_by_the_harness_span_overlapping_most():
    host = sorted([(0, 100, "Engine.step"), (100, 130, "submit"),
                   (130, 400, "idle")])
    assert R._attribute(90, 120, host) == "submit"
    assert R._attribute(50, 95, host) == "Engine.step"
    assert R._attribute(500, 600, host) == "untraced"


@pytest.fixture(scope="module")
def chip(tmp_path_factory):
    return R.reduce(unpacked(tmp_path_factory.mktemp("trace")))


def test_recorded_trace_reduces(chip):
    assert chip.devices == 1
    assert 0 < chip.busy_s <= chip.window_s
    assert {"decode_fn", "prefill_fn"} <= set(chip.program_count)
    # every kernel's time is inside some program
    for (prog, k), ns in chip.in_program.items():
        if k in R.KERNELS:
            assert prog != "?" and ns > 0
    # each decode program calls flash_decode once per block
    n = chip.program_count["decode_fn"]
    assert chip.in_program_count[("decode_fn", "flash_decode")] == 2 * n
    assert chip.in_program_count[("decode_fn", "dequant_matmul")] == 2 * 7 * n
    assert chip.kernel_s("dequant_matmul", "decode_fn") > 0


def test_recorded_trace_breakdown(chip):
    bd = chip.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in bd["device_ops"])
    assert not any(n.split("/")[-1] in R.CONTAINERS
                   for n, _ in bd["device_ops"])
    idle = sum(ns for _, ns in chip.gaps) * 1e-9
    assert idle == pytest.approx(chip.window_s - chip.busy_s, rel=1e-6)


def test_recorded_trace_shares_stay_under_the_roofline(chip):
    from bench.cost import dequant_matmul
    peaks = {"bf16_flop_per_s": 197e12, "hbm_byte_per_s": 819e9}
    for prog in ("decode_fn", "prefill_fn"):
        share = dequant_matmul.roofline_share(chip, (prog,), peaks)
        assert share is not None and 0 < share <= 100
