"""Counted work from the configurations' shapes, and the table of peaks."""

import dataclasses
import importlib.util
import json
from pathlib import Path

import pytest

import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name):
    spec = importlib.util.spec_from_file_location(f"ref_{name}", CONFIGS / f"{name}.py")
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref, json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name, direct, counted, convs, eligible", [
    ("vgg16_224", 30.94, 6.310, 13, 13),
    ("resnet50_224", 8.178, 5.766, 53, 13),
])
def test_forward_work_per_image(name, direct, counted, convs, eligible):
    ref, cfg = config(name)
    w = work.forward_work(ref, cfg, 1)
    assert w["direct_flops"] / 1e9 == pytest.approx(direct, abs=0.005)
    assert w["counted_flops"] / 1e9 == pytest.approx(counted, abs=0.005)
    assert (w["convs"], w["eligible_convs"]) == (convs, eligible)
    # linear in the batch
    w32 = work.forward_work(ref, cfg, 32)
    assert w32["counted_flops"] == pytest.approx(32 * w["counted_flops"])


def test_counted_work_ignores_the_plan(monkeypatch):
    """The count is read off the reference, so no plan (algorithm, m,
    pipeline) that the program chooses can change it."""
    import repro.core.conv as conv
    from repro.core.plan import plan_for_conv

    ref, cfg = config("vgg16_224")
    before = work.forward_work(ref, cfg, 8)
    calls = []

    def other_plan(*a, **kw):
        calls.append(1)
        p = plan_for_conv(*a, **kw)
        return dataclasses.replace(p, algorithm="winograd_nonfused", m=2)

    monkeypatch.setattr(conv, "plan_for_conv", other_plan)
    assert work.forward_work(ref, cfg, 8) == before
    assert not calls


def test_vgg16_least_time_is_bandwidth_bound():
    ref, cfg = config("vgg16_224")
    peak = work.peaks("TPU v5 lite")
    ops = work.forward_ops(ref, cfg, 32)
    t = work.least_time(ops, peak)
    flops_only = sum(o["counted"] for o in ops if o["eligible"]) / peak["bf16_flops_per_s"]
    assert t == pytest.approx(3.61e-3, rel=0.01)
    assert t > flops_only


def test_peaks_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
