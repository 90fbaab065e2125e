"""Gradient correctness at the conv2d API boundary (DESIGN.md SS8).

``jax.grad`` through every pipeline vs the VJP of
``jax.lax.conv_general_dilated`` (the golden reference), across dtypes,
ragged shapes, the pad >= r regression range, and -- under the
``host_mesh8`` fixture -- the mesh-routed path, where the test also
asserts the custom VJP actually ran (both backward GEMMs observed at the
executor boundary as GemmAssignments, never differentiate-through-
shard_map).

The F(r, m) filter-gradient pipeline itself is checked against XLA's
filter gradient on every Table-1 layer shape (channels exact, spatial
scaled -- the benchmark convention; the full-scale sweep is the `slow`
tier).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv2d
from repro.core import winograd as wg

PIPELINES = ["winograd_nonfused", "winograd_fused", "winograd_fused_e2e"]

TOL = {
    "float32": dict(atol=2e-3, rtol=2e-3),
    "bfloat16": dict(atol=1e-1, rtol=1e-1),
}


def _lax_conv(x, w, pad):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _ref_grads(x, w, pad):
    f = lambda x_, w_: jnp.sum(jnp.sin(_lax_conv(x_, w_, pad)))
    return jax.grad(f, argnums=(0, 1))(x.astype(jnp.float32),
                                       w.astype(jnp.float32))


def _data(N, H, W, C, K, dtype=jnp.float32, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (N, H, W, C), jnp.float32).astype(dtype)
    w = (jax.random.uniform(kw, (3, 3, C, K), jnp.float32, -1, 1)
         / np.sqrt(9 * C)).astype(dtype)
    return x, w


def _check(algorithm, x, w, pad, m, tol, **conv_kw):
    f = lambda x_, w_: jnp.sum(jnp.sin(
        conv2d(x_, w_, pad=pad, algorithm=algorithm, m=m, **conv_kw)))
    gx, gw = jax.grad(f, argnums=(0, 1))(x, w)
    gx_ref, gw_ref = _ref_grads(x, w, pad)
    assert gx.dtype == x.dtype and gw.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(gx, np.float32),
                               np.asarray(gx_ref, np.float32),
                               err_msg=f"{algorithm} dx", **tol)
    np.testing.assert_allclose(np.asarray(gw, np.float32),
                               np.asarray(gw_ref, np.float32),
                               err_msg=f"{algorithm} dw", **tol)


# ------------------------- pipeline gradchecks -------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("algorithm", PIPELINES)
def test_pipeline_grads_match_lax(algorithm, dtype):
    """jax.grad of every Pallas pipeline == lax grads (ragged 9x11)."""
    x, w = _data(1, 9, 11, 3, 5, jnp.dtype(dtype), seed=7)
    _check(algorithm, x, w, pad=1, m=2, tol=TOL[dtype])


@pytest.mark.parametrize("algorithm", ["winograd", "auto"])
def test_reference_and_auto_grads(algorithm):
    """The jnp reference path (XLA autodiff) and whatever "auto" plans."""
    x, w = _data(2, 12, 12, 4, 6, seed=11)
    _check(algorithm, x, w, pad=1, m=None if algorithm == "auto" else 4,
           tol=TOL["float32"])


@pytest.mark.parametrize("pad", list(range(4)), ids=lambda p: f"pad{p}")
def test_backward_pad_range(pad):
    """Regression (PR3 satellite): dx for pad >= r used a negative
    backward pad, corrupting the full-correlation.  pad in {0..r}."""
    x, w = _data(1, 8, 9, 3, 4, seed=pad)
    _check("winograd_fused", x, w, pad=pad, m=2, tol=TOL["float32"])


# ---------------------- filter-gradient pipeline ----------------------


def _xla_dw(x, gy, K, pad):
    _, vjp = jax.vjp(
        lambda w_: _lax_conv(x, w_, pad),
        jnp.zeros((3, 3, x.shape[-1], K), jnp.float32))
    return vjp(gy)[0]


def _filter_grad_layer_sweep(scale):
    from repro.models.cnn import TABLE1_LAYERS

    for spec in TABLE1_LAYERS:
        h = max(8, int(spec.H * scale))
        kx, kg = jax.random.split(jax.random.PRNGKey(spec.C))
        x = jax.random.normal(kx, (1, h, h, spec.C), jnp.float32)
        P = h + 2 * spec.pad - spec.r + 1
        gy = jax.random.normal(kg, (1, P, P, spec.K), jnp.float32)
        ref = _xla_dw(x, gy, spec.K, spec.pad)
        for m in (2, 4):
            got = wg.winograd_filter_grad_reference(
                x, gy, r=spec.r, m=m, pad=spec.pad)
            scale_ref = float(jnp.max(jnp.abs(ref)))
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref),
                atol=1e-4 * max(scale_ref, 1.0), rtol=2e-3,
                err_msg=f"{spec.name} m={m}")


def test_filter_grad_exact_on_table1_layers():
    """F(r, m) dw == XLA dw, fp32, all Table-1 layers (spatial / 8)."""
    _filter_grad_layer_sweep(0.125)


@pytest.mark.slow
def test_filter_grad_exact_on_table1_layers_fullscale():
    _filter_grad_layer_sweep(1.0)


def test_filter_grad_pallas_kernel_path():
    """kernels.ops.conv2d_filter_grad (Pallas GEMM core) == XLA dw."""
    from repro.kernels import ops

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 11, 5), jnp.float32)
    gy = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 11, 7), jnp.float32)
    ref = _xla_dw(x, gy, 7, 1)
    got = ops.conv2d_filter_grad(x, gy, r=3, m=2, pad=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_grad_transforms_dual_structure():
    """F(r, m) shares the forward's B^T (same evaluation points) and its
    exact algebra verifies like the forward's (Cook-Toom exactness)."""
    from repro.core.transforms import (exact_correlation_check,
                                       grad_transform_arrays,
                                       transform_arrays)

    for m in (2, 4, 6):
        _, _, BT = transform_arrays(m, 3, "float64")
        ATg, Gg, BTg = grad_transform_arrays(m, 3, "float64")
        np.testing.assert_array_equal(BT, BTg)
        assert ATg.shape == (3, m + 2) and Gg.shape == (m + 2, m)
        assert exact_correlation_check(3, m)  # F(r, m) is exact


def test_grad_plan_cached_and_consistent():
    """GradPlan: cached like forward plans, dx plan is a forward plan for
    the rotated conv, ineligible shapes fall back to direct."""
    from repro.core.plan import (ConvSpec, _grad_plan, clear_plan_cache,
                                 grad_plan)

    clear_plan_cache()
    spec = ConvSpec(N=1, H=28, W=28, C=64, K=64, r=3, pad=1)
    gp = grad_plan(spec)
    assert gp.algorithm == "winograd_grad" and gp.m in (2, 4, 6)
    assert gp.dw_blocks is not None
    assert gp.dx is not None and gp.dx.spec.C == spec.K and gp.dx.spec.K == spec.C
    gp2 = grad_plan(spec)
    assert gp2 is gp and _grad_plan.cache_info().hits >= 1
    strided = ConvSpec(N=1, H=28, W=28, C=8, K=8, r=3, stride=2)
    assert grad_plan(strided).algorithm == "direct"


# ------------------------- mesh-routed gradients -------------------------


@pytest.mark.parametrize("mode", ["data", "2d", "model"])
def test_sharded_grads_match_lax(host_mesh8, mode):
    """jax.grad through conv2d(mesh=...) == lax grads for every forced
    mode, including a ragged-T layer."""
    for (N, H, W, C, K) in [(1, 14, 14, 16, 24), (1, 9, 11, 4, 6)]:
        x, w = _data(N, H, W, C, K, seed=C)
        _check("winograd", x, w, pad=1, m=4, tol=TOL["float32"],
               mesh=host_mesh8, parallel_mode=mode)


def test_sharded_grad_takes_custom_vjp(host_mesh8, monkeypatch):
    """The mesh path differentiates through the custom VJP: both backward
    GEMMs arrive at the executor as GemmAssignments (the backward-aware
    PartitionSpecs), not via differentiate-through-shard_map."""
    from repro.parallel import executor

    seen = []
    orig = executor.execute_gemm

    def spy(V, U, **kw):
        seen.append(kw["mode"])
        return orig(V, U, **kw)

    monkeypatch.setattr(executor, "execute_gemm", spy)
    x, w = _data(1, 14, 14, 8, 8, seed=0)
    f = lambda x_, w_: jnp.sum(conv2d(x_, w_, pad=1, algorithm="winograd",
                                      m=4, mesh=host_mesh8,
                                      parallel_mode="2d") ** 2)
    jax.grad(f, argnums=(0, 1))(x, w)
    assignments = [s for s in seen if isinstance(s, executor.GemmAssignment)]
    assert len(assignments) == 2, seen          # dx GEMM + dw GEMM
    dx_a, dw_a = executor.grad_assignments("2d")
    assert set(assignments) == {dx_a, dw_a}
    # forward "2d" makes the dw GEMM exactly the "model" spec-triple:
    # contraction over "data" with a psum of partials (DESIGN.md SS8)
    assert dw_a.red == "data" and dw_a.col == "model"


def test_cnn_train_step_sharded_loss_drops(host_mesh8):
    """The PR's workload: a VGG block trains on the mesh with Winograd
    forward and backward sharded, and the loss goes down."""
    from repro.launch.workloads import build_cnn_workload, run_cnn_workload

    wl = build_cnn_workload("vgg16", mesh=host_mesh8, batch=8, hw=32,
                            n_classes=4, width_mult=0.0625)
    state, out = run_cnn_workload(wl, steps=10)
    assert int(state.step) == 10
    h = out["loss_history"]
    assert min(h[-3:]) < h[0], h
