"""ConvPlan: the single decision layer for every convolution call.

Before this layer, the choices that shape a conv call were scattered:
algorithm dispatch in ``core/conv.py``, F(m, r) selection in
``blocking.select_tile_m``, block sizes in ``blocking.choose_blocks``, and
the parallel mode in ``parallel/strategy.choose_mode`` -- each re-derived
ad hoc at every call site.  ``plan(spec)`` folds them into one cached,
hashable decision (DESIGN.md SS5):

    ConvSpec  --plan()-->  ConvPlan(algorithm, m, BlockConfig,
                                    parallel_mode, t_est, hbm_bytes, flops)

The planner evaluates a two-term roofline (MXU compute, HBM traffic) over
the candidate space {F(2,3), F(4,3), F(6,3)} x {fused_e2e, fused} and
returns the argmin; ineligible shapes plan to "direct".  Plans are
lru-cached on the frozen spec, which is what lets a serving engine
amortize selection across millions of requests: repeated layer shapes cost
one dict lookup (``plan_cache_info`` exposes the hit counters).

The same layer owns the LM-workload decisions (``plan_lm``): parallel mode
and gradient-accumulation depth by model scale, consumed by
``launch/workloads.py``.

Layering: this module may import ``blocking`` and ``parallel.strategy``
(the cost *mechanisms*); everything else -- conv dispatch, kernels/ops,
models, launch, serve, benchmarks -- consumes plans and makes no blocking/
mode/m decision of its own.
"""

from __future__ import annotations

import dataclasses
import functools

from . import blocking, hw
from . import winograd as wg

#: conv2d algorithm name per kernel pipeline (DESIGN.md SS3).
PIPELINE_ALGORITHM = {
    "fused_e2e": "winograd_fused_e2e",
    "fused": "winograd_fused",
    "nonfused": "winograd_nonfused",
}
ALGORITHM_PIPELINE = {v: k for k, v in PIPELINE_ALGORITHM.items()}


def eligible(r1: int, r2: int, stride: int) -> bool:
    """Winograd eligibility: square filter, supported r, stride 1.  The
    single definition -- ``core.conv.winograd_eligible`` wraps it."""
    return r1 == r2 and stride == 1 and 2 <= r1 <= 7


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Hashable description of one conv2d problem (NHWC x HWIO)."""

    N: int
    H: int
    W: int
    C: int
    K: int
    r: int = 3
    stride: int = 1
    pad: int = 0
    elt_bytes: int = 4
    r2: int | None = None  # second filter dim when non-square (ineligible)

    @classmethod
    def for_conv(cls, x_shape, w_shape, *, stride: int = 1, pad: int = 0,
                 elt_bytes: int = 4) -> "ConvSpec":
        N, H, W, C = x_shape
        r1, r2 = int(w_shape[0]), int(w_shape[1])
        return cls(N=int(N), H=int(H), W=int(W), C=int(C), K=int(w_shape[-1]),
                   r=r1, stride=int(stride), pad=int(pad),
                   elt_bytes=int(elt_bytes), r2=None if r1 == r2 else r2)

    @property
    def winograd_eligible(self) -> bool:
        return eligible(self.r, self.r if self.r2 is None else self.r2,
                        self.stride)

    def tiles(self, m: int) -> tuple[int, int, int]:
        """(T, tH, tW) for F(m, r) -- the paper's xi tile count."""
        P = max(self.H + 2 * self.pad - self.r + 1, 1)
        Q = max(self.W + 2 * self.pad - self.r + 1, 1)
        tH = max(-(-P // m), 1)
        tW = max(-(-Q // m), 1)
        return self.N * tH * tW, tH, tW


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """One resolved conv decision: everything a call site needs, nothing it
    has to re-derive.  Frozen + hashable so plans can key jit caches."""

    spec: ConvSpec
    algorithm: str                        # conv2d algorithm name
    m: int | None                         # F(m, r) tile size (None: direct)
    blocks: blocking.BlockConfig | None   # kernel blocking (None: direct)
    parallel_mode: str                    # "data" | "2d" | "model"
    t_est: float                          # modeled step seconds (roofline)
    hbm_bytes: int                        # modeled end-to-end HBM traffic
    flops: int

    @property
    def pipeline(self) -> str | None:
        return ALGORITHM_PIPELINE.get(self.algorithm)

    def kernel_kwargs(self) -> dict:
        return {} if self.blocks is None else self.blocks.as_kwargs()


def _direct_plan(spec: ConvSpec, mesh: tuple[int, ...]) -> ConvPlan:
    r2 = spec.r2 if spec.r2 is not None else spec.r
    P = max((spec.H + 2 * spec.pad - spec.r) // spec.stride + 1, 1)
    Q = max((spec.W + 2 * spec.pad - r2) // spec.stride + 1, 1)
    flops = 2 * spec.N * P * Q * spec.K * spec.C * spec.r * r2
    bytes_ = spec.elt_bytes * (
        spec.N * spec.H * spec.W * spec.C
        + spec.r * r2 * spec.C * spec.K
        + spec.N * P * Q * spec.K
    )
    t = max(flops / hw.PEAK_FLOPS_F32, bytes_ / hw.HBM_BW)
    return ConvPlan(spec, "direct", None, None, "data", t, bytes_, flops)


@functools.lru_cache(maxsize=4096)
def _plan(spec: ConvSpec, candidates: tuple[int, ...],
          mesh: tuple[int, ...]) -> ConvPlan:
    if not spec.winograd_eligible:
        return _direct_plan(spec, mesh)

    elt = spec.elt_bytes
    best: ConvPlan | None = None
    for m in candidates:
        a = m + spec.r - 1
        L = a * a
        T, _, _ = spec.tiles(m)
        flops = wg.winograd_stage_flops(
            spec.N, spec.H, spec.W, spec.C, spec.K, spec.r, m,
            pad=spec.pad)["total"]
        tiles_bytes = T * L * spec.C * elt     # tile-extraction write
        # fused_e2e first so ties break toward the single-pass pipeline
        for pipeline in ("fused_e2e", "fused"):
            cfg = blocking.choose_blocks(T, spec.C, spec.K, m, spec.r, elt,
                                         pipeline=pipeline)
            if cfg is None:
                continue  # V-cache does not fit: e2e ineligible here
            traffic = tiles_bytes + cfg.pipeline_bytes(pipeline)
            t = max(flops / hw.PEAK_FLOPS_F32, traffic / hw.HBM_BW)
            if best is None or t < best.t_est:
                best = ConvPlan(spec, PIPELINE_ALGORITHM[pipeline], m, cfg,
                                "data", t, traffic, flops)
    if best is None:  # no candidate fit anywhere: stay on the XLA path
        return _direct_plan(spec, mesh)

    from repro.parallel.strategy import choose_mode  # mechanism, not policy

    a = best.m + spec.r - 1
    T, _, _ = spec.tiles(best.m)
    mode = choose_mode(T, spec.C, spec.K, a * a, elt=elt, mesh=mesh)
    return dataclasses.replace(best, parallel_mode=mode)


def plan(spec: ConvSpec, *, candidates: tuple[int, ...] = (2, 4, 6),
         mesh: tuple[int, ...] = hw.POD_MESH) -> ConvPlan:
    """The single decision point: ConvSpec -> cached ConvPlan."""
    return _plan(spec, tuple(candidates), tuple(mesh))


def plan_for_conv(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
                  elt_bytes: int = 4,
                  mesh: tuple[int, ...] = hw.POD_MESH) -> ConvPlan:
    """Convenience entry used by ``core.conv.conv2d``.

    ``mesh`` is the (dp, tp) extent the conv will actually execute on --
    the parallel-mode argmin is mesh-dependent, so a mesh-routed call
    must plan for its own mesh, not the production default.
    """
    return plan(ConvSpec.for_conv(x_shape, w_shape, stride=stride, pad=pad,
                                  elt_bytes=elt_bytes), mesh=tuple(mesh))


def plan_cache_info():
    return _plan.cache_info()


def grad_plan_cache_info():
    return _grad_plan.cache_info()


def clear_plan_cache() -> None:
    _plan.cache_clear()
    _grad_plan.cache_clear()


# ------------------------- gradient planning (SS8) -------------------------
#
# The backward pass runs two more Winograd-shaped workloads per conv:
#
#   dx -- a full-correlation of gy with the rotated, C/K-swapped filter:
#         literally another conv2d problem, so its plan IS a forward
#         ConvPlan for the (gy, w_rot) shapes;
#   dw -- the F(r, m) filter-gradient GEMM dU(L, C, K) = X~(L, C, T) x
#         Gy(L, T, K): the same batched-GEMM core with the contraction
#         moved to T, so its blocking reuses ``choose_blocks`` with the
#         (rows, contraction, cols) = (C, T, K) role mapping.
#
# Like forward plans, gradient plans are resolved once per layer shape and
# lru-cached -- a training run re-traces the same conv shapes every step,
# so the backward decisions must be one dict lookup, not a re-derivation.


@dataclasses.dataclass(frozen=True)
class GradPlan:
    """Resolved backward-pass decisions for one conv2d problem."""

    spec: ConvSpec                        # the FORWARD problem
    algorithm: str                        # "winograd_grad" | "direct"
    m: int | None                         # F(r, m) tile size for dw (None: XLA)
    dw_blocks: blocking.BlockConfig | None  # dU-GEMM blocking, (C, T, K) roles
    dx: ConvPlan | None                   # plan for the rotated-filter dx conv
    t_est: float                          # modeled dw step seconds
    flops: int                            # dw GEMM + transform FLOPs
    # ---- single-pass fused backward variant (kernels/wino_fused_bwd) ----
    # Planned at the FORWARD m (the fused kernel shares the saved x tiling),
    # with its own VMEM model and axis candidates.  bwd_algorithm is
    # "fused_bwd" when the working set fits the budget, else "two_pass".
    bwd_algorithm: str = "two_pass"
    bwd_blocks: blocking.BlockConfig | None = None
    hbm_bytes_bwd_fused: int = 0          # modeled single-pass traffic
    hbm_bytes_bwd_two_pass: int = 0       # modeled PR-3 two-pass traffic
    t_bwd_est: float = 0.0                # modeled fused dx+dw seconds


def _grad_direct(spec: ConvSpec) -> GradPlan:
    return GradPlan(spec, "direct", None, None, None, 0.0, 0)


def _fused_bwd_fields(spec: ConvSpec, m: int) -> dict:
    """Plan the single-pass fused backward at the forward tile size ``m``."""
    elt = spec.elt_bytes
    r = spec.r
    a = m + r - 1
    L = a * a
    T, _, _ = spec.tiles(m)
    cfg = blocking.choose_bwd_blocks(T, spec.C, spec.K, m, r, elt)
    if cfg is None:
        return dict(bwd_algorithm="two_pass")
    two_pass = blocking.hbm_traffic_bwd_two_pass(
        L, m, T, spec.C, spec.K, cfg.block_t, cfg.block_c, cfg.block_k, elt)
    # dx + dw GEMMs are each the forward GEMM's FLOPs; both transforms and
    # the gy-side adjoint ride along (small next to the contractions).
    flops = 2 * (2 * L * T * spec.C * spec.K)
    t = max(flops / hw.PEAK_FLOPS_F32, cfg.hbm_bytes_fused / hw.HBM_BW)
    return dict(bwd_algorithm="fused_bwd", bwd_blocks=cfg,
                hbm_bytes_bwd_fused=cfg.hbm_bytes_fused,
                hbm_bytes_bwd_two_pass=two_pass, t_bwd_est=t)


@functools.lru_cache(maxsize=4096)
def _grad_plan(spec: ConvSpec, candidates: tuple[int, ...],
               mesh: tuple[int, ...]) -> GradPlan:
    if not spec.winograd_eligible:
        return _grad_direct(spec)
    elt = spec.elt_bytes
    r = spec.r
    P = max(spec.H + 2 * spec.pad - r + 1, 1)
    Q = max(spec.W + 2 * spec.pad - r + 1, 1)
    best: tuple[float, int, blocking.BlockConfig] | None = None
    for m in candidates:
        a = m + r - 1
        L = a * a
        T, _, _ = spec.tiles(m)
        # dU GEMM: rows=C, contraction=T, cols=K
        cfg = blocking.choose_blocks(spec.C, T, spec.K, m, r, elt,
                                     pipeline="nonfused")
        if cfg is None:
            continue
        gemm = 2 * L * T * spec.C * spec.K
        # transform flops: x-side (shared with fwd) + gy-side + inverse
        tr = 2 * T * spec.C * (a * a * a * 2) + 2 * T * spec.K * (a * m * (m + a)) \
            + 2 * spec.C * spec.K * (a * r * (a + r))
        flops = gemm + tr
        # traffic: d tiles + gy tiles + GEMM streams + dU + dw
        bytes_ = (T * L * spec.C + T * m * m * spec.K) * elt \
            + cfg.hbm_bytes_nonfused
        t = max(flops / hw.PEAK_FLOPS_F32, bytes_ / hw.HBM_BW)
        if best is None or t < best[0]:
            best = (t, m, cfg, flops)
    if best is None:
        return _grad_direct(spec)
    t, m, cfg, flops = best
    # dx: a forward-planned conv of gy (N, P, Q, K) with w_rot (r, r, K, C).
    # pad >= r makes the effective backward pad negative; the kernel layer
    # computes with max(pad_b, 0) and crops, so plan for that padding.
    dx_plan = plan(ConvSpec(N=spec.N, H=P, W=Q, C=spec.K, K=spec.C, r=r,
                            pad=max(r - 1 - spec.pad, 0), elt_bytes=elt),
                   candidates=candidates, mesh=mesh)
    # The fused single-pass backward pairs with the FORWARD plan: it re-tiles
    # the saved x at the forward m, so it is planned there, not at the dw m.
    fwd = plan(spec, candidates=candidates, mesh=mesh)
    bwd = (_fused_bwd_fields(spec, fwd.m)
           if fwd.pipeline == "fused_e2e" else dict(bwd_algorithm="two_pass"))
    return GradPlan(spec, "winograd_grad", m, cfg, dx_plan, t, flops, **bwd)


def grad_plan(spec: ConvSpec, *, candidates: tuple[int, ...] = (2, 4, 6),
              mesh: tuple[int, ...] = hw.POD_MESH) -> GradPlan:
    """The backward-pass decision point: ConvSpec -> cached GradPlan."""
    return _grad_plan(spec, tuple(candidates), tuple(mesh))


def grad_plan_for_conv(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
                       elt_bytes: int = 4,
                       mesh: tuple[int, ...] = hw.POD_MESH) -> GradPlan:
    """Convenience entry mirroring ``plan_for_conv`` for the backward pass."""
    return grad_plan(ConvSpec.for_conv(x_shape, w_shape, stride=stride,
                                       pad=pad, elt_bytes=elt_bytes),
                     mesh=tuple(mesh))


def grad_kernel_blocks(C: int, T: int, K: int, m: int, r: int,
                       elt_bytes: int) -> blocking.BlockConfig:
    """Blocking for the dU(L, C, K) = X~(L, C, T) x Gy(L, T, K) GEMM.

    The plan-layer entry for ``kernels.ops.conv2d_filter_grad`` (which sees
    the GEMM extents, not N/H/W): rows=C, contraction=T, cols=K mapped onto
    ``choose_blocks``' (T, C, K) slots.
    """
    return kernel_blocks(C, T, K, m, r, elt_bytes, pipeline="nonfused")


def bwd_kernel_blocks(T: int, C: int, K: int, m: int, r: int,
                      elt_bytes: int = 4) -> blocking.BlockConfig | None:
    """Blocking for the single-pass fused backward kernel -- the plan-layer
    entry for ``kernels.ops.conv2d_fused_bwd`` (which sees the tiled
    extents).  Returns None when the fused working set cannot fit the VMEM
    budget; callers must then take the two-pass backward."""
    return blocking.choose_bwd_blocks(T, C, K, m, r, elt_bytes)


def kernel_blocks(T: int, C: int, K: int, m: int, r: int, elt_bytes: int,
                  pipeline: str = "fused") -> blocking.BlockConfig:
    """Blocking decision for an already-tiled problem -- the plan-layer
    entry point for ``kernels/ops.py`` (which sees T, not N/H/W).

    Raises ``ValueError`` when no blocking of ``pipeline`` fits the VMEM
    budget (for "fused_e2e", a full-C V-cache too large at every tile
    block): the compiler would refuse the kernel.  ``plan`` never selects
    a pipeline in that regime.
    """
    cfg = blocking.choose_blocks(T, C, K, m, r, elt_bytes, pipeline=pipeline)
    if cfg is None:
        raise ValueError(
            f"no {pipeline} blocking of T={T} C={C} K={K} F({m},{r}) fits "
            f"the {blocking.hw.VMEM_BUDGET} B VMEM budget")
    return cfg


# ----------------------- LM workload planning (C6) -----------------------

@dataclasses.dataclass(frozen=True)
class LMWorkloadSpec:
    """Scale-level description of an LM workload (arch x run shape)."""

    n_params: float
    is_moe: bool
    kind: str          # "train" | "prefill" | "decode"
    batch: int


@dataclasses.dataclass(frozen=True)
class LMWorkloadPlan:
    spec: LMWorkloadSpec
    parallel_mode: str     # "2d" | "dp" | "tp" logical mesh view
    microbatches: int


@functools.lru_cache(maxsize=None)
def plan_lm(spec: LMWorkloadSpec) -> LMWorkloadPlan:
    """C6 analogue at LM scale: parallel mode + grad-accumulation depth.

    Small dense models (fit one chip several times over) train pure-DP
    with ZeRO-1 state sharding; everything else keeps 2-D TP+DP.  Decode
    keeps "2d" (the split-K cache sharding needs the model axis).
    Training at B>=64 microbatches 8x (16x above 50B params) to keep
    per-layer remat carries small.
    """
    if spec.kind == "train" and spec.n_params <= 10e9 and not spec.is_moe:
        mode = "dp"
    else:
        mode = "2d"
    if spec.kind != "train" or spec.batch < 64:
        mb = 1
    else:
        mb = 16 if spec.n_params > 50e9 else 8
    return LMWorkloadPlan(spec, mode, mb)
