"""The per-layer readers on made-up traces: what each reads, and that a
reader with nothing to read returns None."""

import types

import pytest

import harness

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
# two eligible convs of one batch (least times 3 s and 2 s) and one other
OPS = [{"eligible": True, "counted": 300.0, "bytes": 10.0},
       {"eligible": True, "counted": 100.0, "bytes": 20.0},
       {"eligible": False, "counted": 1e6, "bytes": 1e6}]


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               f"metric_{name}")


def view(ops: dict, requests: int = 4):
    trace = {"ops": ops, "busy_s": 9.0, "window_s": 10.0}
    return types.SimpleNamespace(
        trace=trace, record={"requests": requests, "batch": 1}, peak=PEAK,
        ops=lambda batch: OPS)


def test_conv_roofline_over_the_kernels_of_every_eligible_conv():
    roof = reader("conv_roofline.offline")
    # 4 batches x (3 + 2) s of least time over 40 s of kernels
    v = view({"wino_fused_e2e.1": 15.0, "wino_gemm.2": 15.0,
              "filter_transform.1": 10.0, "fusion.3": 99.0})
    assert roof.read(v) == pytest.approx(50.0)


def test_conv_roofline_is_silent_when_an_eligible_conv_has_no_kernel():
    roof = reader("conv_roofline.offline")
    # one eligible conv went to XLA: only one GEMM-carrying kernel is left
    v = view({"wino_fused_e2e.1": 15.0, "filter_transform.1": 10.0,
              "convolution.4": 15.0})
    assert roof.read(v) is None
    assert roof.read(view({})) is None


def test_idle_share():
    idle = reader("idle_share.offline")
    assert idle.read(view({})) == pytest.approx(10.0)
    assert idle.read(types.SimpleNamespace(trace=None)) is None
