"""``scoped``: device self time by conv, route and stage, read from each
operation's name-scope path (the ``tf_op`` of its event metadata), and
the two readers built on it.

``testdata/small.xplane.pb`` was recorded before the program named its
convs; ``testdata/scoped/small.xplane.pb`` by the same ``record_trace.py``
after: three calls of one planned 64->64 Winograd conv at 28x28, batch 2,
on a TPU v5e, with the ReLU outside the conv."""

import types
from pathlib import Path

import pytest

import harness
import scoped
import xtrace
from test_correct import cpu_plan, tiny_cell  # noqa: F401  (a fixture)

DATA = Path(__file__).resolve().parents[1] / "testdata"
RECORDINGS = [DATA / "small.xplane.pb", DATA / "scoped" / "small.xplane.pb"]
CONV = "conv_r3s1_c64k64_h28w28"


def tf_ops(path) -> dict[str, str]:
    (events,) = scoped.device_ops(str(path))
    return {name: tf_op for _, _, name, tf_op in events}


def test_reads_the_tf_op_paths_of_the_recording():
    ops = tf_ops(DATA / "small.xplane.pb")
    assert ops["wino_fused_e2e.1"] == ("jit(<lambda>)/jit(conv2d_pallas)/"
                                       "jit(wino_fused_e2e)/wino_fused_e2e/pallas_call:")
    assert ops["pad.4"] == "jit(<lambda>)/jit(conv2d_pallas)/jit(_pad)/pad:"
    assert ops["fusion.1"] == "jit(<lambda>)/jit(conv2d_pallas)/jit(_take)/gather:"
    assert ops["slice_maximum_fusion"] == "jit(<lambda>)/jit(relu)/max:"
    assert ops["copy-done"] == ops["copy-start"] == ""
    # nothing in that program named a conv
    assert set(scoped.scoped(str(DATA / "small.xplane.pb"))) == {scoped.OUTSIDE}


def test_the_scoped_recording_charges_each_stage_to_the_conv():
    path = DATA / "scoped" / "small.xplane.pb"
    ops = tf_ops(path)
    kernels = [n for n in ops if xtrace.family(n) == "wino_fused_e2e"]
    assert kernels
    for name in kernels:
        assert scoped.scope_of(ops[name]) == (CONV, "winograd_fused_e2e", "kernel")
    assert {scoped.scope_of(ops[n]) for n in ops if xtrace.family(n) == "filter_transform"} \
        == {(CONV, "winograd_fused_e2e", "filter")}
    got = scoped.scoped(str(path))
    stages = {stage for key, _, stage in got if key == CONV}
    assert {"tiles", "pad", "filter", "kernel"} <= stages
    # the ReLU is the caller's, outside the conv
    assert got[scoped.OUTSIDE] > 0 and scoped.SHARED not in got


@pytest.mark.parametrize("path", RECORDINGS, ids=lambda p: p.parent.name)
def test_self_times_add_up_to_busy(path):
    busy = xtrace.reduce_trace(str(path))["busy_s"]
    assert sum(scoped.scoped(str(path)).values()) == pytest.approx(busy, rel=1e-3)


def test_self_time_of_an_enclosing_while():
    # a while (0-100) whose body ran two operations, then one that
    # overlaps the next in part, then one inside the other exactly
    events = [(0, 100, "while"), (10, 30, "body"), (40, 60, "body"),
              (200, 260, "a"), (250, 300, "b"), (400, 450, "c"),
              (400, 450, "d")]
    assert scoped.self_ps(events) == {"while": 60, "body": 40, "a": 50,
                                      "b": 50, "d": 50}
    # the sum is the union: 100 + 100 + 50
    assert sum(scoped.self_ps(events).values()) == 250


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(f)/conv_r3s1_c8k8_h10w10/winograd_fused_e2e/jit(conv2d_pallas)/"
     "tiles/jit(_take)/gather:", ("conv_r3s1_c8k8_h10w10", "winograd_fused_e2e", "tiles")),
    ("jit(f)/conv_r3s1_c8k8_h10w10/epilogue/jit(relu)/max:",
     ("conv_r3s1_c8k8_h10w10", "", "epilogue")),
    ("jit(f)/conv_r7s2_c3k64_h224w224/direct/conv_general_dilated:",
     ("conv_r7s2_c3k64_h224w224", "direct", "")),
    # the primitive's own name is never read as a stage
    ("jit(f)/conv_r3s1_c8k8_h10w10/winograd/jit(_pad)/pad:",
     ("conv_r3s1_c8k8_h10w10", "winograd", "")),
    ("jit(f)/conv_r3s1_c8k8_h10w10/winograd_fused_e2e/jit(conv2d_pallas)/"
     "tiles/reshape;reshape:", ("conv_r3s1_c8k8_h10w10", "winograd_fused_e2e", "tiles")),
    ("jit(f)/conv_r3s1_c8k8_h10w10/epilogue/add;"
     "jit(f)/conv_r1s1_c8k8_h10w10/epilogue/add:", scoped.SHARED),
    ("jit(f)/jit(relu)/max:", scoped.OUTSIDE),
    ("", scoped.OUTSIDE),
])
def test_scope_of_a_path(tf_op, scope):
    assert scoped.scope_of(tf_op) == scope


def test_conv_key_round_trip():
    key = scoped.conv_key(3, 1, 64, 128, 112, 112)
    assert key == "conv_r3s1_c64k128_h112w112"
    assert scoped.parse_key(key) == (3, 1, 64, 128, 112, 112)
    assert scoped.parse_key("outside") is None


# ------------------------------- readers -------------------------------

PEAK = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
# two eligible convs of one batch (least times 3 s and 2 s) and a 1x1
OPS = [{"eligible": True, "counted": 300.0, "bytes": 10.0,
        "shape": [1, 8, 8, 4, 8, 3, 8, 8]},
       {"eligible": True, "counted": 100.0, "bytes": 20.0,
        "shape": [1, 8, 8, 8, 8, 3, 8, 8]},
       {"eligible": False, "counted": 1e6, "bytes": 1e6,
        "shape": [1, 8, 8, 8, 32, 1, 8, 8]}]
A, B = "conv_r3s1_c4k8_h8w8", "conv_r3s1_c8k8_h8w8"
SCOPED = {(A, "winograd_fused_e2e", "tiles"): 3.0,
          (A, "winograd_fused_e2e", "kernel"): 5.0,
          (A, "winograd_fused_e2e", "filter"): 1.0,
          (A, "", "epilogue"): 1.0,
          (B, "direct", ""): 6.0, (B, "", "epilogue"): 4.0,
          ("conv_r1s1_c8k32_h8w8", "", "epilogue"): 30.0,
          scoped.OUTSIDE: 9.0, scoped.SHARED: 1.0}


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                               f"metric_{name}")


def view(scoped_s, batches=4):
    """A traced run's view whose short traced window (``scoped.traced``)
    is already taken: ``batches`` batches with ``scoped_s`` in them."""
    cell = types.SimpleNamespace(cache={"scoped": {
        "scoped": scoped_s, "busy_s": sum(scoped_s.values()),
        "batches": batches}})
    return types.SimpleNamespace(
        cell=cell, trace={}, record={"requests": 100, "batch": 1},
        peak=PEAK, ops=lambda batch: OPS)


def test_glue_share_is_the_winograd_convs_outside_their_kernels():
    # conv A's tiles and epilogue over 60 s busy; B runs direct
    assert reader("glue_share.offline").read(view(SCOPED)) == pytest.approx(
        100 * 4.0 / 60.0)


def test_wino_conv_roofline_covers_every_3x3_conv_whatever_its_route():
    # 4 batches x (3 + 2) s of least time over A's 10 s and B's 10 s
    assert reader("wino_conv_roofline.offline").read(view(SCOPED)) == \
        pytest.approx(100.0)


def test_readers_are_silent_without_scoped_convs():
    missing_b = {k: v for k, v in SCOPED.items() if k[0] != B}
    assert reader("wino_conv_roofline.offline").read(view(missing_b)) is None
    parent = {scoped.OUTSIDE: 10.0}
    for name in ("glue_share.offline", "wino_conv_roofline.offline"):
        assert reader(name).read(view(parent)) is None
        assert reader(name).read(types.SimpleNamespace(trace=None)) is None


def test_conv_table_adds_up_to_busy():
    line = scoped.conv_table(SCOPED, 60.0, 4)
    assert line.startswith("device self seconds per batch by conv, 4 batches: "
                           "conv_r1s1_c8k32_h8w8 7.5 s (50.0 % of busy")
    assert "outside 2.25 s" in line and line.endswith("all of them 1.0 of busy")


def test_traced_window_runs_the_cells_program_without_compiling(
        monkeypatch, cpu_plan):
    """After a tiny VGG-16 cell's window on the CPU, ``scoped.traced`` sends
    the window's program ``BATCHES`` times under a trace of its own and
    compiles nothing.  The CPU trace has no device plane, so the two
    reductions are stood in for."""
    cell = tiny_cell("vgg16.offline_b32")
    result = harness.run_loop(cell)
    cell.reduced_trace = {}
    busy = {scoped.OUTSIDE: 2.0}
    monkeypatch.setattr(scoped, "scoped", lambda path: busy)
    monkeypatch.setattr(xtrace, "reduce_trace", lambda path: {"busy_s": 2.0})
    got = scoped.traced(harness.View(cell, result))
    assert (got["scoped"], got["busy_s"], got["batches"], got["compiles"]) \
        == (busy, 2.0, scoped.BATCHES, 0)
    assert cell.cache["engine"].params is None
    assert scoped.traced(harness.View(cell, result)) is got
