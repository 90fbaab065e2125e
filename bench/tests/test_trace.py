"""``xtrace.py`` on a trace recorded on a TPU v5e by ``record_trace.py``:
three calls of one planned 64->64 Winograd conv at 28x28, batch 2.  The
checkout's directory in its source locations reads ``<checkout>/``."""

import json
from pathlib import Path

import xtrace

DATA = Path(__file__).resolve().parents[1] / "testdata"


def test_reduction_is_the_recorded_one_every_time():
    path = str(DATA / "small.xplane.pb")
    first = xtrace.reduce_trace(path)
    assert xtrace.reduce_trace(path) == first
    expected = json.loads((DATA / "small.expected.json").read_text())
    assert json.loads(json.dumps(first)) == expected


def test_busy_time_kernels_and_gaps():
    r = xtrace.reduce_trace(str(DATA / "small.xplane.pb"))
    assert r["devices"] == 1
    # every operation ran once per call, three calls
    assert set(r["op_calls"].values()) == {3}
    assert r["op_calls"]["wino_fused_e2e.1"] == 3
    assert r["op_calls"]["filter_transform.1"] == 3
    kernels = xtrace.op_seconds(r, ["wino_fused_e2e", "filter_transform"])
    assert kernels == r["ops"]["wino_fused_e2e.1"] + r["ops"]["filter_transform.1"]
    # busy is the union of the operations, so no more than their sum
    assert 0 < r["busy_s"] <= sum(r["ops"].values())
    # the two gaps between the three calls are the longest, and the host
    # was inside a dispatch span during each
    assert [g[0] for g in r["gaps"][:2]] == ["bench.dispatch"] * 2
    assert r["gaps"][0][1] >= r["gaps"][1][1] > 100 * r["gaps"][2][1]
    bd = xtrace.breakdown(r)
    assert bd["device_ops"][0][0] == "wino_fused_e2e.1"
    assert len(bd["device_ops"]) == len(bd["idle_gaps"]) == 10


def test_kernel_families_are_exact():
    trace = {"ops": {"wino_fused_e2e.3": 1.0, "wino_fused_e2e.4": 2.0,
                     "wino_fused_bwd.1": 4.0, "wino_fused": 8.0}}
    assert xtrace.op_seconds(trace, ["wino_fused"]) == 8.0
    assert xtrace.op_seconds(trace, ["wino_fused_e2e"]) == 3.0
    assert xtrace.instances(trace, ["wino_fused_e2e", "wino_fused"]) == 3


def test_op_name_is_the_hlo_instruction():
    assert xtrace.op_name("%wino_fused_e2e.1 = f32[104,16,64] custom-call(...)") == "wino_fused_e2e.1"
    assert xtrace.op_name("copy-start") == "copy-start"
