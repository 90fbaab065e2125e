"""Direct unit tests for the blocking model: axis candidates (small and
ragged extents), per-pipeline constraints, and the e2e traffic ordering."""

import pytest

from repro.core import blocking
from repro.core.blocking import (
    axis_candidates,
    choose_blocks,
    e2e_vmem_bytes,
    fused_vmem_bytes,
    hbm_traffic,
    hbm_traffic_e2e,
    round_up,
)


def test_axis_candidates_small_extents():
    # size <= granule: one block, sublane-aligned, covering the extent
    assert axis_candidates(4, 8, (64, 128)) == [8]
    assert axis_candidates(8, 8, (64, 128)) == [8]
    assert axis_candidates(72, 128, (128, 256)) == [72]
    assert axis_candidates(100, 128, (128, 256)) == [104]
    assert axis_candidates(1, 128, (128, 256)) == [8]


@pytest.mark.parametrize("size", [130, 196, 200, 300, 513, 1000])
@pytest.mark.parametrize("granule,caps", [
    (8, (64, 128, 256, 512)),
    (128, (128, 256)),
    (128, (128, 256, 512)),
])
def test_axis_candidates_never_exceed_aligned_extent(size, granule, caps):
    """The old logic could propose blocks far past the extent (e.g. a 256
    block for a 130-wide axis); now every candidate is within one sublane
    step of the extent."""
    limit = round_up(size, granule if granule < 128 else 8)
    cands = axis_candidates(size, granule, caps)
    assert cands, (size, granule)
    for c in cands:
        assert 0 < c <= limit
        assert c % (granule if granule < 128 else 8) == 0


def test_axis_candidates_ragged_t_axis():
    # T = 196 (14x14 tiles): caps clamp to the 8-aligned extent, 200
    assert axis_candidates(196, 8, (64, 128, 256, 512)) == [64, 128, 200]


def test_choose_blocks_ragged_dims_fit_extents():
    cfg = choose_blocks(196, 130, 72, 4, 3)
    assert cfg.block_t <= round_up(196, 8)
    assert cfg.block_c <= round_up(130, 8)
    assert cfg.block_k == round_up(72, 8)
    # padded extents divide the blocks (what kernels/ops.py relies on)
    assert round_up(196, cfg.block_t) % cfg.block_t == 0
    assert round_up(130, cfg.block_c) % cfg.block_c == 0


@pytest.mark.parametrize("T,C,K,m", [(64, 8, 8, 2), (196, 130, 72, 4),
                                     (1024, 256, 512, 6)])
def test_choose_blocks_pipelines_and_budget(T, C, K, m):
    for pipeline in blocking.PIPELINES:
        cfg = choose_blocks(T, C, K, m, 3, pipeline=pipeline)
        assert cfg is not None
        a = m + 3 - 1
        L = a * a
        if pipeline == "fused_e2e":
            Cp = round_up(C, cfg.block_c)
            vm = e2e_vmem_bytes(L, m, Cp, cfg.block_t, cfg.block_c,
                                cfg.block_k, 4)
        else:
            vm = fused_vmem_bytes(L, m, cfg.block_t, cfg.block_c,
                                  cfg.block_k, 4)
        assert vm <= blocking.hw.VMEM_BUDGET


def test_choose_blocks_e2e_infeasible_returns_none():
    # C so large the full-C f32 V-cache cannot fit VMEM at any bt
    assert choose_blocks(512, 16384, 128, 6, 3, pipeline="fused_e2e") is None
    # ... while the two-stage pipelines keep their fallback
    assert choose_blocks(512, 16384, 128, 6, 3, pipeline="fused") is not None


def test_bwd_fused_blocks_fit_budget_on_table1_layers():
    """The fused-backward blocking model: for every Table-1 layer (and
    every tile size) the chosen blocks' modeled VMEM is within budget and
    the feasibility signal is sound -- None only when even the smallest
    blocks overflow the budget -- and the layer's planned m is feasible."""
    from repro.core.plan import ConvSpec, plan
    from repro.models.cnn import TABLE1_LAYERS

    for spec in TABLE1_LAYERS:
        planned = plan(ConvSpec(N=1, H=spec.H, W=spec.W, C=spec.C, K=spec.K,
                                r=spec.r, pad=spec.pad)).m
        for m in (2, 4, 6):
            a = m + spec.r - 1
            L = a * a
            P = spec.H + 2 * spec.pad - spec.r + 1
            T = (-(-P // m)) ** 2
            cfg = blocking.choose_bwd_blocks(T, spec.C, spec.K, m, spec.r)
            if cfg is None:
                assert m != planned, (spec.name, m)
                smallest = blocking.bwd_fused_vmem_bytes(
                    L, m, round_up(spec.K, 8), 8, min(spec.C, 128),
                    min(spec.K, 128), 4)
                assert smallest > blocking.hw.VMEM_BUDGET, (spec.name, m)
                continue
            Kp = round_up(spec.K, cfg.block_k)
            vm = blocking.bwd_fused_vmem_bytes(
                L, m, Kp, cfg.block_t, cfg.block_c, cfg.block_k, 4)
            assert vm == cfg.vmem_bytes <= blocking.hw.VMEM_BUDGET, \
                (spec.name, m, vm)
            # padded extents divide the blocks (the kernel contract)
            assert round_up(T, cfg.block_t) % cfg.block_t == 0
            assert round_up(spec.C, cfg.block_c) % cfg.block_c == 0


def test_bwd_fused_infeasible_returns_none():
    # a resident (L, bc, Kp) dU block for K = 65536 at F(6, 3) cannot fit
    assert blocking.choose_bwd_blocks(512, 128, 65536, 6, 3) is None


def test_bwd_fused_traffic_strictly_below_two_pass_on_table1_layers():
    """The PR's roofline claim, pointwise: at the chosen fused-backward
    blocks, modeled single-pass HBM traffic is STRICTLY below the two-pass
    backward for every Table-1 layer -- the fused pass deletes the V and
    Gy/dO^ round trips, the dU round trip, and the gy halo re-extraction
    that dx's second forward pipeline pays."""
    from repro.models.cnn import TABLE1_LAYERS

    for spec in TABLE1_LAYERS:
        for m in (2, 4, 6):
            a = m + spec.r - 1
            L = a * a
            P = spec.H + 2 * spec.pad - spec.r + 1
            T = (-(-P // m)) ** 2
            cfg = blocking.choose_bwd_blocks(T, spec.C, spec.K, m, spec.r)
            if cfg is None:  # no fused blocks fit: the two-pass runs (the
                continue     # budget test above checks that signal)
            fused = blocking.hbm_traffic_bwd_fused(
                L, m, T, spec.C, spec.K,
                cfg.block_t, cfg.block_c, cfg.block_k, 4)
            two_pass = blocking.hbm_traffic_bwd_two_pass(
                L, m, T, spec.C, spec.K,
                cfg.block_t, cfg.block_c, cfg.block_k, 4)
            assert fused == cfg.hbm_bytes_fused
            assert fused < two_pass, (spec.name, m, fused, two_pass)


def test_grad_plan_carries_fused_bwd_variant():
    """GradPlan exposes the fused-backward variant whenever the forward
    plan is fused_e2e: blocks chosen at the FORWARD m, both traffic models
    populated, and the fused model strictly cheaper."""
    from repro.core.plan import ConvSpec, grad_plan, plan

    spec = ConvSpec(N=1, H=28, W=28, C=64, K=64, r=3, pad=1)
    gp = grad_plan(spec)
    fwd = plan(spec)
    if fwd.pipeline == "fused_e2e":
        assert gp.bwd_algorithm == "fused_bwd"
        assert gp.bwd_blocks is not None
        assert 0 < gp.hbm_bytes_bwd_fused < gp.hbm_bytes_bwd_two_pass
        assert gp.t_bwd_est > 0
    # ineligible (strided) shapes never carry a fused-bwd variant
    strided = ConvSpec(N=1, H=28, W=28, C=8, K=8, r=3, stride=2)
    assert grad_plan(strided).bwd_algorithm == "two_pass"
    assert grad_plan(strided).bwd_blocks is None


def test_e2e_traffic_below_fused_pipeline_pointwise():
    """For identical blocks, the single-pass pipeline strictly beats the
    two-stage fused pipeline: it deletes the input-transform round trip
    (d read + V write) and the V re-read per K block, paying only a
    one-block re-prime per K re-entry."""
    for (T, C, K, m) in [(64, 8, 8, 2), (196, 130, 72, 4), (1024, 256, 512, 6),
                         (4096, 1024, 1024, 6)]:
        a = m + 3 - 1
        L = a * a
        bt, bc, bk = 64, min(128, round_up(C, 8)), min(128, round_up(K, 8))
        e2e = hbm_traffic_e2e(L, m, T, C, K, bt, bc, bk, 4)
        fused_pipeline = (hbm_traffic(L, m, T, C, K, bt, bk, 4, fused=True)
                          + blocking.transform_stage_bytes(L, T, C, 4))
        assert e2e < fused_pipeline, (T, C, K, m)


# --------------------- scoped-VMEM limits (no compiler) ---------------------


def _kernel_limits(monkeypatch, fn, *args):
    """Trace ``fn(*args)`` with ``pallas_call`` replaced by a recorder:
    returns [(kernel name, vmem_limit_bytes), ...] as the wrappers pass
    them, without tracing a kernel body or running a compiler."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    seen = []

    def recording_pallas_call(kernel, *, out_shape, name=None,
                              compiler_params=None, **kw):
        seen.append((name, compiler_params.vmem_limit_bytes))
        return lambda *a: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), out_shape)

    # the jit trace caches would replay kernels traced by earlier tests
    jax.clear_caches()
    monkeypatch.setattr(pl, "pallas_call", recording_pallas_call)
    try:
        jax.eval_shape(fn, *args)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    return seen


def test_kernel_vmem_limits_cover_model_on_table1_layers(monkeypatch):
    """For every Table-1 layer and each pipeline the planner chooses from,
    the scoped-VMEM limit every kernel of the pipeline passes is at least
    the blocking model's bytes for its blocks and at most the v5e's VMEM."""
    import jax
    import jax.numpy as jnp

    from repro.core import tiles
    from repro.core.plan import ConvSpec, kernel_blocks, plan
    from repro.kernels import ops, tile_extract
    from repro.models.cnn import TABLE1_LAYERS

    main_kernel = {"fused_e2e": "wino_fused_e2e", "fused": "wino_fused"}
    for spec in TABLE1_LAYERS:
        p = plan(ConvSpec(N=1, H=spec.H, W=spec.W, C=spec.C, K=spec.K,
                          r=spec.r, pad=spec.pad))
        m, a = p.m, p.m + spec.r - 1
        tH, tW, _, _ = tiles.tile_grid(spec.H, spec.W, m, spec.r, spec.pad)
        T = tH * tW
        x = jax.ShapeDtypeStruct((1, spec.H, spec.W, spec.C), jnp.float32)
        w = jax.ShapeDtypeStruct((spec.r, spec.r, spec.C, spec.K),
                                 jnp.float32)
        for pipeline, kernel in main_kernel.items():
            cfg = kernel_blocks(T, spec.C, spec.K, m, spec.r, 4,
                                pipeline=pipeline)
            bt, bc, bk = cfg.block_t, cfg.block_c, cfg.block_k
            model = {
                kernel: cfg.vmem_bytes,
                "filter_transform": blocking.streamed_vmem_bytes(
                    4, (spec.r ** 2, bc, bk), (a * a, bc, bk)),
                "input_transform": blocking.streamed_vmem_bytes(
                    4, (bt, a * a, bc), (a * a, bt, bc)),
                "tile_extract": blocking.streamed_vmem_bytes(
                    4, *tile_extract.step_blocks(
                        1, tH, tW, m, spec.r, tiles.padded_len(tW, m, spec.r),
                        -(-spec.C // bc) * bc, 4)),
            }
            limits = dict(_kernel_limits(
                monkeypatch,
                lambda x, w: ops.conv2d_pallas(
                    x, w, m=m, pad=spec.pad, pipeline=pipeline,
                    interpret=False), x, w))
            assert kernel in limits, (spec.name, pipeline, limits)
            for name, limit in limits.items():
                assert model[name] <= limit <= blocking.hw.VMEM_BYTES, \
                    (spec.name, pipeline, name, model[name], limit)
            assert cfg.vmem_bytes <= blocking.hw.VMEM_BUDGET


def test_explicit_fused_e2e_that_cannot_fit_raises():
    """An explicit fused_e2e request whose full-C V-cache cannot fit the
    VMEM budget raises -- it no longer falls back to other blocks, which
    the compiler would refuse on the chip."""
    import jax
    import jax.numpy as jnp

    from repro.core.plan import kernel_blocks
    from repro.kernels import ops

    with pytest.raises(ValueError, match="fused_e2e"):
        kernel_blocks(512, 65536, 128, 6, 3, 4, pipeline="fused_e2e")
    x = jax.ShapeDtypeStruct((1, 8, 8, 65536), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 3, 65536, 8), jnp.float32)
    with pytest.raises(ValueError, match="fused_e2e"):
        jax.eval_shape(lambda x, w: ops.conv2d_pallas(
            x, w, m=6, pad=1, pipeline="fused_e2e", interpret=False), x, w)
