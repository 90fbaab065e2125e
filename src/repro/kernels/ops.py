"""jit'd wrappers composing the Winograd Pallas kernels into full convs.

Three pipelines, mirroring the paper's comparison (DESIGN.md SS3):

  * ``pipeline="fused_e2e"`` -- the full single-pass pipeline: one kernel
    consumes extracted tiles directly, input transform as GEMM prologue
    (VMEM V-cache), inverse transform as epilogue.  Neither V nor O^ ever
    exists in HBM.
  * ``pipeline="fused"`` -- Algorithm 1 back half: transforms fused with
    packing, GEMM fused with the output transform (contribution C1).
    O^ never exists in HBM; V still round-trips once.
  * ``pipeline="nonfused"`` -- the three-stage baseline (transform / GEMM /
    inverse-transform as separate HBM round trips), i.e. the structure of
    the libraries the paper beats.

Each pipeline names its stages for the profiler (``jax.named_scope``,
below the conv's own scope from ``repro.core.conv.conv2d``): ``tiles``
(tile extraction, ``d`` padded to block multiples as it is written),
``pad`` (the filter padded to block multiples), ``filter`` (the filter
transform), ``kernel`` (the transforms and the Winograd-domain GEMM) and
``crop`` (cropping and assembling the output).

All consume the same extracted-tile layout; blocking comes from the
ConvPlan layer (``repro.core.plan.kernel_blocks`` -- the single decision
point).  Zero-padding of T/C/K up to block multiples replaces the paper's
dual (alpha, eta) edge-case micro-kernels: on the MXU, ragged tails are
handled by padding to sublane alignment, and zero rows/columns pass
through the bilinear algorithm exactly (DESIGN.md SS2).
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.core import tiles as tiling
from repro.core.blocking import PIPELINES, BlockConfig, round_up

from . import common
from .filter_transform import filter_transform
from .grad_transform import grad_output_transform
from .input_transform import input_transform
from .output_transform import output_transform
from .tile_extract import tile_extract
from .wino_fused import wino_fused
from .wino_fused_bwd import wino_fused_bwd
from .wino_fused_e2e import wino_fused_e2e
from .wino_gemm import wino_gemm


def _pad_dims(T: int, C: int, K: int, cfg: BlockConfig) -> tuple[int, int, int]:
    return (
        round_up(T, cfg.block_t),
        round_up(C, cfg.block_c),
        round_up(K, cfg.block_k),
    )


def _tile_matrix(x: jax.Array, m: int, r: int, pad: int, Tp: int, Cp: int,
                 interpret: bool | None) -> jax.Array:
    """x (N, H, W, C) -> d (Tp, alpha^2, Cp): the kernels' input tiles,
    zero past the T tiles and the C channels.

    C is zero-padded on the image, in the same pass as its spatial
    padding, where it costs a tile's (m/alpha)^2 share of padding ``d``.
    The ``tile_extract`` kernel then writes ``d`` in one pass, T padding
    included.
    """
    x = common.pad_axis_to(x, 3, Cp)
    xp, tH, tW, _, _ = tiling.pad_for_tiles(x, m, r, pad)
    return tile_extract(xp, m=m, r=r, tH=tH, tW=tW, Tp=Tp, interpret=interpret)


# Trace-time switch routing custom-VJP backwards through the PR-3 two-pass
# path.  Read when the backward is TRACED (like ``executor.use_mesh``'s
# ambient mesh), so wrap the whole grad/train-step call, not the apply.
# Exists for golden fused-vs-two-pass comparisons and A/B benchmarking;
# production traces take the fused single-pass backward whenever it fits.
_FORCE_TWO_PASS_BWD = False


@contextlib.contextmanager
def force_two_pass_backward():
    global _FORCE_TWO_PASS_BWD
    prev = _FORCE_TWO_PASS_BWD
    _FORCE_TWO_PASS_BWD = True
    try:
        yield
    finally:
        _FORCE_TWO_PASS_BWD = prev


def two_pass_backward_forced() -> bool:
    return _FORCE_TWO_PASS_BWD


@functools.partial(
    jax.jit,
    static_argnames=("m", "pad", "fused", "pipeline", "interpret",
                     "block_t", "block_c", "block_k"),
)
def conv2d_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    m: int = 6,
    pad: int = 0,
    fused: bool | None = None,
    pipeline: str = "fused",
    interpret: bool | None = None,
    block_t: int | None = None,
    block_c: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Winograd convolution, Pallas path.  x (N,H,W,C), w (r,r,C,K) -> NHWC.

    ``fused`` is kept for back compat (True -> "fused", False ->
    "nonfused"); ``pipeline`` selects among the three pipelines above.
    """
    if fused is not None:
        pipeline = "fused" if fused else "nonfused"
    assert pipeline in PIPELINES, pipeline
    r = w.shape[0]
    assert w.shape[0] == w.shape[1]
    N, H, W, C = x.shape
    K = w.shape[-1]

    # Winograd-domain tensors are held in f32 for sub-f32 inputs: the
    # transform matrices amplify operand rounding by O(2^m) (A^T rows for
    # F(6,3) reach 32), so a bf16 U or V costs ~3 output digits while the
    # input storage rounding itself is benign.  Matches the reference
    # path's compute_dtype and the paper's fp32-throughout arithmetic.
    out_dtype = x.dtype
    tH, tW, P, Q = tiling.tile_grid(H, W, m, r, pad)
    T = N * tH * tW

    # ---- blocking (plan layer; paper SS3.2.2 analogue) ----
    from repro.core.plan import kernel_blocks  # deferred: keeps import acyclic

    elt = max(x.dtype.itemsize, 4)
    cfg = kernel_blocks(T, C, K, m, r, elt, pipeline=pipeline)
    if block_t is not None or block_c is not None or block_k is not None:
        cfg = BlockConfig(
            block_t or cfg.block_t, block_c or cfg.block_c, block_k or cfg.block_k,
            0, 0, 0,
        )
    Tp, Cp, Kp = _pad_dims(T, C, K, cfg)

    with jax.named_scope("tiles"):
        if x.dtype.itemsize < 4:
            x, w = x.astype(jnp.float32), w.astype(jnp.float32)

        # ---- tile extraction (OLA), padded to (Tp, a*a, Cp) ----
        d = _tile_matrix(x, m, r, pad, Tp, Cp, interpret)
    with jax.named_scope("pad"):
        w_flat = w.reshape(r * r, C, K)
        w_flat = common.pad_axis_to(common.pad_axis_to(w_flat, 1, Cp), 2, Kp)

    # ---- filter transform (packing fused in) ----
    with jax.named_scope("filter"):
        U = filter_transform(w_flat, m=m, r=r, block_c=cfg.block_c,
                             block_k=cfg.block_k, interpret=interpret)

    with jax.named_scope("kernel"):
        if pipeline == "fused_e2e":
            # ---- single pass: transform prologue + GEMM + inverse epilogue ----
            y = wino_fused_e2e(
                d, U, m=m, r=r,
                block_t=cfg.block_t, block_k=cfg.block_k, block_c=cfg.block_c,
                interpret=interpret, out_dtype=out_dtype,
            )
        else:
            # ---- input transform (separate HBM round trip for V) ----
            V = input_transform(d, m=m, r=r, block_t=cfg.block_t,
                                block_c=cfg.block_c, interpret=interpret)
            # ---- GEMM (+ fused inverse transform) ----
            if pipeline == "fused":
                y = wino_fused(
                    V, U, m=m, r=r,
                    block_t=cfg.block_t, block_k=cfg.block_k, block_c=cfg.block_c,
                    interpret=interpret, out_dtype=out_dtype,
                )
            else:
                O_hat = wino_gemm(
                    V, U,
                    block_t=cfg.block_t, block_k=cfg.block_k, block_c=cfg.block_c,
                    interpret=interpret,
                )
                y = output_transform(
                    O_hat, m=m, r=r,
                    block_t=cfg.block_t, block_k=cfg.block_k,
                    interpret=interpret, out_dtype=out_dtype,
                )

    # ---- crop padding, assemble spatial output ----
    with jax.named_scope("crop"):
        y = y[:T, :, :K].reshape(T, m, m, K)
        return tiling.assemble_output(y, N, tH, tW, P, Q)


# ----------------------- sharded (mesh) pipeline -----------------------
#
# The distributed form of the same contract: tile extraction and the
# (linear, cheap) transforms run as jnp ops, and the Winograd-domain
# batched GEMM -- the paper's dominant stage -- executes under shard_map
# with the PartitionSpecs of the plan's parallel mode
# (``repro.parallel.executor``, DESIGN.md SS6).  jnp transforms rather
# than the Pallas ones because the sharded path must run on any mesh
# (simulated host CPUs included) without interpret-mode overhead inside
# every shard; on TPU the executor's local_fn hook swaps the per-shard
# matmul for the fused kernel.


@functools.partial(jax.jit, static_argnames=("m", "pad", "mode", "mesh"))
def conv2d_sharded(
    x: jax.Array,
    w: jax.Array,
    *,
    m: int,
    pad: int = 0,
    mesh,
    mode: str = "data",
) -> jax.Array:
    """Winograd conv with the GEMM sharded over ``mesh`` per ``mode``."""
    from repro.core import winograd as wg
    from repro.parallel.executor import execute_gemm

    r = w.shape[0]
    assert w.shape[0] == w.shape[1]
    in_dtype = x.dtype
    with jax.named_scope("tiles"):
        x32, w32 = x.astype(jnp.float32), w.astype(jnp.float32)
        N = x.shape[0]
        xp, tH, tW, P, Q = tiling.pad_for_tiles(x32, m, r, pad)
        d = tiling.flatten_tiles(tiling.extract_tiles(xp, m, r, tH, tW))
    with jax.named_scope("kernel"):
        V = wg.input_transform(d, m, r)                    # (L, T, C)
    with jax.named_scope("filter"):
        U = wg.filter_transform(w32, m, r)                 # (L, C, K)
    with jax.named_scope("kernel"):
        O_hat = execute_gemm(V, U, mode=mode, mesh=mesh)   # (L, T, K) f32
        y = wg.output_transform(O_hat, m, r)               # (T, m, m, K)
    with jax.named_scope("crop"):
        return tiling.assemble_output(y, N, tH, tW, P, Q).astype(in_dtype)


# ------------------- differentiable sharded pipeline -------------------
#
# The custom VJP that makes ``conv2d(..., mesh=...)`` trainable end to end
# WITHOUT differentiating through the shard_map: both backward GEMMs are
# explicit ``execute_gemm`` calls under the backward-aware PartitionSpecs
# of ``parallel.executor.grad_assignments`` -- every tensor keeps its
# forward placement, only the GEMM roles permute (the "model"-mode psum
# changes axis in the gradient; DESIGN.md SS8 table).


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def conv2d_sharded_ad(x: jax.Array, w: jax.Array, m: int, pad: int,
                      mesh, mode: str = "data") -> jax.Array:
    """Differentiable ``conv2d_sharded``: same forward, exact Winograd VJP
    with the dx and dw GEMMs sharded under ``grad_assignments(mode)``."""
    return conv2d_sharded(x, w, m=m, pad=pad, mesh=mesh, mode=mode)


def _sharded_fwd(x, w, m, pad, mesh, mode):
    return conv2d_sharded_ad(x, w, m, pad, mesh, mode), (x, w)


def _sharded_bwd(m, pad, mesh, mode, res, gy):
    if _FORCE_TWO_PASS_BWD:
        return _sharded_bwd_two_pass(m, pad, mesh, mode, res, gy)
    return _sharded_bwd_fused(m, pad, mesh, mode, res, gy)


def _sharded_bwd_fused(m, pad, mesh, mode, res, gy):
    """Single-pass sharded backward: the adjoint formulation of the fused
    kernel, distributed.  gy is transformed ONCE into the Winograd domain
    and both gradient GEMMs contract against the same V/U/dO^ -- no second
    forward pipeline over gy and no second x-side transform.  The dx GEMM's
    (rows, contraction, cols) = (T, K, C) roles match ``grad_assignments``'
    dx assignment natively, so every tensor keeps its forward placement
    for all three mesh modes (DESIGN.md SS8 table)."""
    from repro.core import winograd as wg
    from repro.parallel.executor import execute_gemm, grad_assignments

    x, w = res
    r = w.shape[0]
    dx_asn, dw_asn = grad_assignments(mode)
    x32 = x.astype(jnp.float32)
    gy32 = gy.astype(jnp.float32)
    N, H, Wd, _ = x.shape

    # ---- shared Winograd-domain operands, each built exactly once ----
    xp, tH, tW, P, Q = tiling.pad_for_tiles(x32, m, r, pad)
    d = tiling.flatten_tiles(tiling.extract_tiles(xp, m, r, tH, tW))
    V = wg.input_transform(d, m, r)                       # (L, T, C)
    U = wg.filter_transform(w.astype(jnp.float32), m, r)  # (L, C, K)
    gy_t = tiling.extract_output_tiles(gy32, m, tH, tW)   # (T, m, m, K)
    dO = wg.output_transform_adjoint(gy_t, m, r)          # (L, T, K)

    # ---- dx: dV = dO^ x U^T (contraction K), inverse + OLA epilogue ----
    dV = execute_gemm(dO, jnp.transpose(U, (0, 2, 1)),
                      mode=dx_asn, mesh=mesh)             # (L, T, C)
    dd = wg.input_transform_adjoint(dV, m, r)             # (T, a, a, C)
    dx = tiling.overlap_add_tiles(dd, N, tH, tW, m, r, H, Wd, pad)

    # ---- dw: dU = V^T x dO^ (contraction T), filter-grad epilogue ----
    dU = execute_gemm(jnp.transpose(V, (0, 2, 1)), dO,
                      mode=dw_asn, mesh=mesh)             # (L, C, K)
    dw = wg.filter_transform_adjoint(dU, m, r)
    return dx.astype(x.dtype), dw.astype(w.dtype)


def _sharded_bwd_two_pass(m, pad, mesh, mode, res, gy):
    """The PR-3 two-pass sharded backward: golden reference / A-B baseline."""
    from repro.core import winograd as wg
    from repro.parallel.executor import execute_gemm, grad_assignments

    x, w = res
    r = w.shape[0]
    dx_asn, dw_asn = grad_assignments(mode)
    gy32 = gy.astype(jnp.float32)

    # ---- dx: rotated-filter Winograd conv, GEMM contracting K ----
    dx = _dx_via_rotated_conv(
        lambda g, wr, s: conv2d_sharded(g, wr, m=m, pad=s, mesh=mesh,
                                        mode=dx_asn),
        gy32, w, x.shape[1], x.shape[2], pad)

    # ---- dw: F(r, m) filter-gradient pipeline, GEMM contracting T ----
    x32 = x.astype(jnp.float32)
    xp, tH, tW, P, Q = tiling.pad_for_tiles(x32, m, r, pad)
    d = tiling.flatten_tiles(tiling.extract_tiles(xp, m, r, tH, tW))
    V = wg.input_transform(d, m, r)                       # (L, T, C)
    gy_t = tiling.extract_output_tiles(gy32, m, tH, tW)   # (T, m, m, K)
    Gy = wg.grad_output_transform(gy_t, m, r)             # (L, T, K)
    dU = execute_gemm(jnp.transpose(V, (0, 2, 1)), Gy,
                      mode=dw_asn, mesh=mesh)             # (L, C, K)
    dw = wg.filter_grad_inverse(dU, m, r)
    return dx.astype(x.dtype), dw.astype(w.dtype)


conv2d_sharded_ad.defvjp(_sharded_fwd, _sharded_bwd)


# ----------------------- exact filter gradient -----------------------
#
# The F(r, m) filter-gradient pipeline on the Pallas kernel core
# (DESIGN.md SS8): the x-side transform is the forward input transform
# (B^T is shared between F(m, r) and F(r, m) -- same evaluation points),
# the gy-side transform runs the F(r, m) filter-transform kernel, and the
# contraction over tiles is the SAME L-batched GEMM kernel as the forward
# with the roles permuted:
#
#     dU(L, C, K) = X~(L, C, T) x Gy(L, T, K)      (wino_gemm, rows=C,
#                                                    contraction=T, cols=K)
#
# The inverse transform onto the r x r tap grid (A'^T dU A') contracts a
# tensor that is K*C small -- it stays a jnp einsum, like the epilogue
# scale/shift of the serving stack.


@functools.partial(jax.jit, static_argnames=("r", "m", "pad", "interpret"))
def conv2d_filter_grad(
    x: jax.Array,
    gy: jax.Array,
    *,
    r: int,
    m: int = 4,
    pad: int = 0,
    interpret: bool | None = None,
) -> jax.Array:
    """Exact Winograd filter gradient: x (N,H,W,C), gy (N,P,Q,K) -> (r,r,C,K).

    Matches the VJP of the framework convolution w.r.t. the HWIO filter;
    the Winograd-domain tensors are held in f32 (same rounding-amplification
    argument as the forward pipelines).  Returns f32; callers cast.
    """
    from repro.core import winograd as wg
    from repro.core.plan import grad_kernel_blocks  # deferred: import acyclic

    x = x.astype(jnp.float32)
    gy = gy.astype(jnp.float32)
    N, H, W, C = x.shape
    K = gy.shape[-1]
    tH, tW, _, _ = tiling.tile_grid(H, W, m, r, pad)
    T = N * tH * tW

    # ---- blocking (plan layer): rows=C, contraction=T, cols=K ----
    cfg = grad_kernel_blocks(C, T, K, m, r, elt_bytes=4)
    Cp = round_up(C, cfg.block_t)
    Tp = round_up(T, cfg.block_c)
    Kp = round_up(K, cfg.block_k)

    # ---- tiling: overlapping x tiles + non-overlapping gy tiles ----
    d = _tile_matrix(x, m, r, pad, Tp, Cp, interpret)
    gy_t = tiling.extract_output_tiles(gy, m, tH, tW)    # (T, m, m, K)
    gy_t = gy_t.reshape(T, m * m, K)
    gy_t = common.pad_axis_to(common.pad_axis_to(gy_t, 0, Tp), 2, Kp)

    # ---- transforms (Pallas): X~ = B^T d B, Gy = G' gy G'^T ----
    V = input_transform(d, m=m, r=r, block_t=cfg.block_c, block_c=cfg.block_t,
                        interpret=interpret)             # (L, Tp, Cp)
    Gy = grad_output_transform(gy_t, m=m, r=r, block_t=cfg.block_c,
                               block_k=cfg.block_k, interpret=interpret)

    # ---- the gradient GEMM on the forward GEMM kernel ----
    # transpose_lhs: the (L, Tp, Cp) X~ is read contraction-major through a
    # transposed-read BlockSpec -- the (L, Cp, Tp) copy never materializes.
    dU = wino_gemm(V, Gy, transpose_lhs=True,
                   block_t=cfg.block_t, block_k=cfg.block_k,
                   block_c=cfg.block_c, interpret=interpret)  # (L, Cp, Kp)

    # ---- inverse onto the r x r filter taps ----
    return wg.filter_grad_inverse(dU[:, :C, :K], m, r)


# ------------------- single-pass fused backward -------------------
#
# The backward mirror of the fused_e2e forward (DESIGN.md SS8): ONE kernel
# pass computes dx and dw together from the saved (x, w) and gy.  gy is
# transformed once into the Winograd domain, both gradients contract
# against a shared VMEM V-cache built from x, and the inverse/filter-grad
# transforms run as epilogues -- no V, Gy/dO^, or dU HBM round trip.


def fused_bwd_eligible(x_shape, w_shape, m: int, pad: int) -> bool:
    """True when the single-pass backward's working set fits VMEM (the
    resident dU block is the hard constraint).  Static-shape decision,
    taken at trace time by ``_bwd``/callers; False routes to two-pass."""
    from repro.core.plan import bwd_kernel_blocks  # deferred: import acyclic

    N, H, W, C = x_shape
    r = int(w_shape[0])
    K = int(w_shape[-1])
    tH, tW, P, Q = tiling.tile_grid(H, W, m, r, pad)
    if P < 1 or Q < 1:
        return False
    T = N * tH * tW
    return bwd_kernel_blocks(T, C, K, m, r) is not None


@functools.partial(jax.jit, static_argnames=("m", "pad", "interpret"))
def conv2d_fused_bwd(
    x: jax.Array,
    w: jax.Array,
    gy: jax.Array,
    *,
    m: int,
    pad: int = 0,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Single-pass Winograd backward: (x, w, gy) -> (dx, dw), one kernel.

    Winograd-domain tensors are held in f32 (same rounding-amplification
    argument as the forward); returns f32, callers cast.  Callers must
    check ``fused_bwd_eligible`` first -- this asserts feasibility.
    """
    from repro.core import winograd as wg
    from repro.core.plan import bwd_kernel_blocks  # deferred: import acyclic

    r = w.shape[0]
    a = m + r - 1
    N, H, W, C = x.shape
    K = w.shape[-1]
    x32 = x.astype(jnp.float32)
    gy32 = gy.astype(jnp.float32)
    tH, tW, _, _ = tiling.tile_grid(H, W, m, r, pad)
    T = N * tH * tW

    # ---- blocking (plan layer): the fused-backward model ----
    cfg = bwd_kernel_blocks(T, C, K, m, r)
    assert cfg is not None, "check fused_bwd_eligible before calling"
    Tp, Cp, Kp = _pad_dims(T, C, K, cfg)

    # ---- tiling: overlapping x tiles + non-overlapping gy tiles ----
    d = _tile_matrix(x32, m, r, pad, Tp, Cp, interpret)
    gy_t = tiling.extract_output_tiles(gy32, m, tH, tW).reshape(T, m * m, K)
    gy_t = common.pad_axis_to(common.pad_axis_to(gy_t, 0, Tp), 2, Kp)
    w_flat = w.astype(jnp.float32).reshape(r * r, C, K)
    w_flat = common.pad_axis_to(common.pad_axis_to(w_flat, 1, Cp), 2, Kp)
    U = filter_transform(w_flat, m=m, r=r, block_c=cfg.block_c,
                         block_k=cfg.block_k, interpret=interpret)

    # ---- the single pass: dd and dU in one grid launch ----
    dd, dU = wino_fused_bwd(
        d, gy_t, U, m=m, r=r, block_t=cfg.block_t, block_c=cfg.block_c,
        block_k=cfg.block_k, interpret=interpret)

    # ---- epilogues outside the kernel: OLA add + r x r inverse ----
    dd = dd[:T, :, :C].reshape(T, a, a, C)
    dx = tiling.overlap_add_tiles(dd, N, tH, tW, m, r, H, W, pad)
    dw = wg.filter_transform_adjoint(dU[:, :C, :K], m, r)
    return dx, dw


# --------------------- differentiable wrapper ---------------------
#
# The transforms are linear, so the exact backward pass is two more
# Winograd pipelines: dL/dx is a full-correlation with the
# channel-transposed, 180deg-rotated filter -- run through the same Pallas
# forward pipeline -- and dL/dw is the F(r, m) filter-gradient pipeline
# above.  For the fused_e2e pipeline both collapse into the single-pass
# fused backward whenever its working set fits VMEM; the two-pass pair
# stays as the fallback and the golden reference
# (``force_two_pass_backward``).  Both of the training step's heavy
# backward GEMMs therefore run on the optimized kernels (DESIGN.md SS8).


def _dx_via_rotated_conv(conv_fn, gy: jax.Array, w: jax.Array,
                         H: int, W: int, pad: int) -> jax.Array:
    """dL/dx as a full correlation of gy with the rotated, C/K-swapped
    filter, through ``conv_fn(gy, w_rot, pad=...)``.

    The effective backward padding r - 1 - pad goes negative once
    pad >= r; padding is non-negative in the kernel contract, so compute
    with the clamped pad and crop the surplus border (exact: the cropped
    rows are the out-of-range taps a negative pad would have skipped).
    The single definition for both the Pallas and the sharded backward.
    """
    r = w.shape[0]
    w_rot = jnp.transpose(w[::-1, ::-1, :, :], (0, 1, 3, 2))  # (r, r, K, C)
    pad_b = r - 1 - pad
    s = max(pad_b, 0)
    dx = conv_fn(gy, w_rot, s)
    crop = s - pad_b
    if crop:
        dx = dx[:, crop:crop + H, crop:crop + W, :]
    return dx


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def conv2d_pallas_ad(x: jax.Array, w: jax.Array, m: int, pad: int,
                     pipeline: str = "fused"):
    if isinstance(pipeline, bool):  # legacy fused flag
        pipeline = "fused" if pipeline else "nonfused"
    return conv2d_pallas(x, w, m=m, pad=pad, pipeline=pipeline)


def _fwd(x, w, m, pad, pipeline):
    return conv2d_pallas_ad(x, w, m, pad, pipeline), (x, w)


def _bwd(m, pad, pipeline, res, gy):
    x, w = res
    r = w.shape[0]
    if isinstance(pipeline, bool):
        pipeline = "fused" if pipeline else "nonfused"
    # single-pass fused backward: the backward mirror of the e2e forward
    if (pipeline == "fused_e2e" and not _FORCE_TWO_PASS_BWD
            and fused_bwd_eligible(x.shape, w.shape, m, pad)):
        dx, dw = conv2d_fused_bwd(x, w, gy, m=m, pad=pad)
        return dx.astype(x.dtype), dw.astype(w.dtype)
    return _bwd_two_pass(m, pad, pipeline, x, w, gy)


def _bwd_two_pass(m, pad, pipeline, x, w, gy):
    """The PR-3 two-pass backward: fallback and golden reference."""
    r = w.shape[0]
    # dx: rotated-filter full correlation through the same Pallas pipeline
    dx = _dx_via_rotated_conv(
        lambda g, wr, s: conv2d_pallas(g, wr, m=m, pad=s, pipeline=pipeline),
        gy, w, x.shape[1], x.shape[2], pad)
    # dw: exact F(r, m) Winograd filter gradient on the Pallas GEMM core
    dw = conv2d_filter_grad(x, gy, r=r, m=m, pad=pad).astype(w.dtype)
    return dx, dw


conv2d_pallas_ad.defvjp(_fwd, _bwd)
