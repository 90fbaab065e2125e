"""The correctness check on the CPU, at a small size, with the chip check
skipped: sound runs come out correct, and the control (the reference in
three-pass bf16, put in the program's place) and an answer altered where
it is produced come out not correct under the cell's own limits.

The sizes here are cut (widths, image size, batch) so that a test run can
hold them; the cells run at the published sizes.  The planner's Pallas
pipelines become the jnp Winograd convolution here (``cpu_plan``): the
kernels in the CPU's interpreter would take minutes.
"""

from __future__ import annotations

import copy
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

import harness
import refs

TINY = {
    "vgg16_224": {"image_size": 32, "fc": [64, 64],
                  "stages": [[2, 8], [2, 16], [3, 16], [3, 32], [3, 32]]},
    "resnet50_224": {"image_size": 32, "stem_channels": 8,
                     "stages": [[1, 8], [2, 16], [1, 32], [1, 64]]},
}
TRAFFIC = {"offline": {"batch": 4, "pool": 2}}


@pytest.fixture(autouse=True)
def cpu_plan(monkeypatch):
    import repro.core.conv as conv

    plan_for_conv = conv.plan_for_conv

    def jnp_winograd(*a, **kw):
        p = plan_for_conv(*a, **kw)
        return p if p.algorithm == "direct" else dataclasses.replace(
            p, algorithm="winograd")

    monkeypatch.setattr(conv, "plan_for_conv", jnp_winograd)


def tiny_cell(workload: str, seed: int = 12345678901) -> harness.Cell:
    cell = harness.cell_for(workload, seed, 0.5, False, 0.0, jax.devices()[:1])
    cell.cfg = {**copy.deepcopy(cell.cfg), **TINY[cell.cfg["name"]]}
    cell.traffic = {**cell.traffic, **TRAFFIC[cell.traffic["kind"]]}
    return cell


def verdict(cell) -> tuple[bool, dict]:
    result = harness.run_loop(cell)
    return harness.judge(result, harness.limits(cell.workload["name"]))


def control_forward(cell):
    """The reference at the next precision down, in the program's place."""
    fwd = functools.partial(cell.ref.forward, conv=refs.conv("high"),
                            matmul=refs.matmul("high"))
    return lambda params, x, algorithm="auto": fwd(params, x)


SERVED = ["vgg16.offline_b32", "resnet50.offline_b32"]


@pytest.mark.parametrize("workload", SERVED)
def test_sound_run_is_correct(workload):
    ok, checks = verdict(tiny_cell(workload))
    assert ok, checks


@pytest.mark.parametrize("workload", SERVED)
def test_control_is_not_correct(workload):
    cell = tiny_cell(workload)
    cell.__dict__["forward"] = control_forward(cell)
    ok, checks = verdict(cell)
    assert not ok, checks


FAULTS = {
    # one logit of one answer changed where it is produced
    "altered_answer": lambda y: y.at[0, 0].add(0.5),
    # half of the batch left out: its rows are copies of the other half's
    "half_batch": lambda y: jnp.concatenate([y[: len(y) // 2]] * 2),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", SERVED)
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    from repro.serve import ConvServeEngine

    infer = ConvServeEngine.infer
    calls = []

    # set-up calls infer once to compile and once per pool batch to time
    fault_at = 1 + TRAFFIC["offline"]["pool"] + 2

    def broken(self, images):
        y = infer(self, images)
        calls.append(1)
        return FAULTS[fault](y) if len(calls) == fault_at else y

    monkeypatch.setattr(ConvServeEngine, "infer", broken)
    ok, checks = verdict(tiny_cell(workload))
    assert len(calls) > fault_at
    assert not ok, checks
