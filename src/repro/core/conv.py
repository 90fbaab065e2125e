"""Public convolution API: the paper's technique as a first-class framework op.

``conv2d`` exposes every algorithm the paper measures, under one signature:

  algorithm = "direct"           XLA direct convolution (accuracy ground truth)
            | "im2col"           im2col + one GEMM (classic GEMM conv)
            | "winograd"         pure-JAX Winograd (reference path, auto-diff)
            | "winograd_tewmm"   NNPACK-style tuple-element-wise multiply
            | "winograd_nonfused"  three-stage Pallas pipeline (NCNN-like)
            | "winograd_fused"   Algorithm 1: GEMM fused with output transform
            | "winograd_fused_e2e" the full single-pass pipeline: input
                                 transform as GEMM prologue, inverse as
                                 epilogue -- V and O^ never touch HBM
            | "auto"             resolved by the ConvPlan layer
                                 (``repro.core.plan``): algorithm, F(m, r)
                                 and blocking from one cached cost model

Every decision (algorithm, m, blocking, parallel mode) is made by
``plan(spec)`` -- this module only *dispatches* (DESIGN.md SS5).

When a mesh is active -- passed as ``conv2d(..., mesh=...)`` or installed
ambiently via ``repro.parallel.executor.use_mesh`` (the serving engine
does this) -- every Winograd-eligible call routes through the executor:
the Winograd-domain GEMM runs under shard_map with the PartitionSpecs of
the plan's ``parallel_mode`` (paper C6 executed, DESIGN.md SS6).  The
mesh path is differentiable end to end: ``differentiable=True`` (the
default) binds a custom VJP whose dx and dw GEMMs also run under the
executor, with the backward-aware PartitionSpecs dual to the forward
mode (DESIGN.md SS8) -- training never differentiates through shard_map.

Eligibility for Winograd: square filter, r in {2,3,5...}, stride 1, groups 1.

Every call names its operations for the profiler (``jax.named_scope``;
metadata only, nothing runs differently): one scope per call,
``conv_scope(x.shape, w.shape, stride)``, and below it one component
naming the route the call resolved to -- the algorithm, or
``winograd_sharded`` for the mesh path.  The Pallas and mesh pipelines
name their stages below that (``repro.kernels.ops``), and
``repro.models.cnn.conv_block`` puts its bias, affine and ReLU under the
same conv scope in stage ``epilogue``.  A profiler trace then ties every
device operation to its conv by the operation's name-scope path.
"""

from __future__ import annotations

from typing import Literal

import jax

from . import winograd as wg
from .plan import ALGORITHM_PIPELINE, eligible, plan_for_conv

Algorithm = Literal[
    "direct", "im2col", "winograd", "winograd_tewmm",
    "winograd_nonfused", "winograd_fused", "winograd_fused_e2e", "auto",
]


def winograd_eligible(w_shape: tuple, stride: int) -> bool:
    return eligible(w_shape[0], w_shape[1], stride)


def conv_scope(x_shape: tuple, w_shape: tuple, stride: int) -> str:
    """The name scope of one conv: ``conv_r{r}s{stride}_c{C}k{K}_h{H}w{W}``.

    H and W are the input's spatial size before padding.  Convs of one
    shape share the name, so their device time adds up under it: the unit
    a plan is made for.
    """
    _, H, W, C = x_shape
    r, _, _, K = w_shape
    return f"conv_r{r}s{stride}_c{C}k{K}_h{H}w{W}"


#: algorithms whose Winograd-domain GEMM the executor can shard.
_SHARDABLE = ("winograd", "winograd_tewmm", "winograd_nonfused",
              "winograd_fused", "winograd_fused_e2e")


def conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    pad: int = 0,
    algorithm: Algorithm = "auto",
    m: int | None = None,
    differentiable: bool = True,
    mesh=None,
    parallel_mode: str | None = None,
) -> jax.Array:
    """2-D convolution (cross-correlation), NHWC x HWIO -> NHWC.

    ``mesh``/``parallel_mode`` activate the sharded execution path; with
    ``parallel_mode=None`` the mode comes from ``ConvPlan.parallel_mode``.
    """
    if mesh is None:
        from repro.parallel import executor  # deferred: core stays importable

        mesh, ambient_mode = executor.active_mesh()
        parallel_mode = parallel_mode or ambient_mode

    # Only consult the planner when a decision is actually needed: "auto"
    # dispatch, a Winograd algorithm called without an explicit m, or a
    # mesh-routed call (shardable, else the mode would be discarded)
    # without an explicit mode.  Mesh-routed plans are made for the mesh
    # the conv will execute on -- the mode argmin is mesh-dependent.
    needs_m = m is None and algorithm not in ("direct", "im2col")
    needs_mode = (mesh is not None and parallel_mode is None and stride == 1
                  and (algorithm == "auto" or algorithm in _SHARDABLE))
    if algorithm == "auto" or needs_m or needs_mode:
        mesh_shape = (tuple(mesh.shape.get(a, 1) for a in ("data", "model"))
                      if mesh is not None else None)
        p = plan_for_conv(x.shape, w.shape, stride=stride, pad=pad,
                          elt_bytes=x.dtype.itemsize,
                          **({"mesh": mesh_shape} if mesh_shape else {}))
        if algorithm == "auto":
            algorithm = p.algorithm
        if m is None:
            m = p.m if p.m is not None else 4
        if needs_mode:
            parallel_mode = p.parallel_mode

    sharded = mesh is not None and algorithm in _SHARDABLE and stride == 1
    with (jax.named_scope(conv_scope(x.shape, w.shape, stride)),
          jax.named_scope("winograd_sharded" if sharded else algorithm)):
        if sharded:
            from repro.kernels import ops  # deferred: keeps core importable w/o kernels

            if differentiable:
                # custom VJP: dx and dw run under the backward-aware
                # PartitionSpecs of the mode (never differentiate-through-
                # shard_map; DESIGN.md SS8)
                return ops.conv2d_sharded_ad(x, w, m, pad, mesh,
                                             parallel_mode or "data")
            return ops.conv2d_sharded(x, w, m=m, pad=pad, mesh=mesh,
                                      mode=parallel_mode or "data")

        if algorithm == "direct":
            return wg.direct_conv2d(x, w, pad=pad, stride=stride)

        assert stride == 1, f"{algorithm} requires stride 1"
        if algorithm == "im2col":
            return wg.im2col_conv2d(x, w, pad=pad)
        if algorithm == "winograd":
            return wg.winograd_conv2d_reference(x, w, m, pad=pad)
        if algorithm == "winograd_tewmm":
            return wg.winograd_conv2d_reference(x, w, m, pad=pad, use_tewmm=True)
        if algorithm in ALGORITHM_PIPELINE:
            from repro.kernels import ops  # deferred: keeps core importable w/o kernels

            pipeline = ALGORITHM_PIPELINE[algorithm]
            if differentiable:
                return ops.conv2d_pallas_ad(x, w, m, pad, pipeline)
            return ops.conv2d_pallas(x, w, m=m, pad=pad, pipeline=pipeline)
        raise ValueError(f"unknown algorithm {algorithm!r}")


def conv1d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    pad: int = 0,
    algorithm: str = "auto",
    m: int = 4,
) -> jax.Array:
    """1-D convolution, NWC x WIO -> NWC.  Winograd F(m, r) when eligible."""
    r = w.shape[0]
    if algorithm == "auto":
        algorithm = "winograd" if (stride == 1 and 2 <= r <= 7) else "direct"
    if algorithm == "direct":
        return wg.direct_conv1d(x, w, pad=pad, stride=stride)
    assert stride == 1
    return wg.winograd_conv1d_reference(x, w, m, pad=pad)
