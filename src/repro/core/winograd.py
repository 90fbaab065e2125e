"""Pure-JAX Winograd convolution (the algorithmic reference path).

This module is the framework's *algorithm-level* implementation of the
paper's method: the full pipeline Eq. (3)/(4) expressed with jnp/einsum so
that (a) it is the oracle the Pallas kernels are validated against, (b) it is
automatically differentiable (the transforms are linear maps, so XLA autodiff
yields the exact transposed-Winograd backward pass), and (c) it runs
anywhere.  The performance path (kernels/ops.py) implements the same
contract with Pallas TPU kernels and a custom VJP that falls back to this
module's transpose.

Tensor conventions:
  x : (N, H, W, C)  NHWC
  w : (r, r, C, K)  HWIO
  y : (N, P, Q, K)
Winograd-domain:
  V : (L, T, C)   transformed input   (L = alpha^2, T = N*tH*tW)
  U : (L, C, K)   transformed filter
  O^: (L, T, K)   GEMM result
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import tiles as tiling
from .transforms import grad_transform_arrays, transform_arrays

#: Precision of every f32 contraction of the Winograd path -- this
#: reference, the mesh path and the Pallas kernels.  On a TPU an f32 dot at
#: default precision takes bf16 passes (Mosaic's too), and the transforms
#: amplify operand rounding by O(2^m), so all of them ask for full f32.
PRECISION = jax.lax.Precision.HIGHEST
_einsum = functools.partial(jnp.einsum, precision=PRECISION)


def _consts(m: int, r: int, dtype=jnp.float32):
    AT, G, BT = transform_arrays(m, r, "float32")
    return (
        jnp.asarray(AT, dtype=dtype),
        jnp.asarray(G, dtype=dtype),
        jnp.asarray(BT, dtype=dtype),
    )


def _grad_consts(m: int, r: int, dtype=jnp.float32):
    """F(r, m) matrices for the filter gradient of forward F(m, r)."""
    ATg, Gg, BTg = grad_transform_arrays(m, r, "float32")
    return (
        jnp.asarray(ATg, dtype=dtype),  # (r, alpha)
        jnp.asarray(Gg, dtype=dtype),   # (alpha, m)
        jnp.asarray(BTg, dtype=dtype),  # (alpha, alpha) == forward B^T
    )


# --------------------------- stage primitives ---------------------------

def input_transform(tiles: jax.Array, m: int, r: int) -> jax.Array:
    """(T, alpha, alpha, C) -> V (L, T, C) :  V = B^T d B, vectorized over C.

    The (x, y) Winograd coordinates are flattened into the leading L axis --
    the paper's Eq. (4) coordinate collapse, which makes the GEMM stage an
    L-batched matmul.
    """
    _, _, BT = _consts(m, r, tiles.dtype)
    # d: (T, i, j, C);  V[x,y] = sum_ij BT[x,i] BT[y,j] d[i,j]
    v = _einsum("xi,tijc,yj->xytc", BT, tiles, BT)
    a = BT.shape[0]
    return v.reshape(a * a, *v.shape[2:])  # (L, T, C)


def filter_transform(w: jax.Array, m: int, r: int) -> jax.Array:
    """(r, r, C, K) -> U (L, C, K) : U = G g G^T."""
    _, G, _ = _consts(m, r, w.dtype)
    u = _einsum("xi,ijck,yj->xyck", G, w, G)
    a = G.shape[0]
    return u.reshape(a * a, *u.shape[2:])


def batched_gemm(V: jax.Array, U: jax.Array) -> jax.Array:
    """O^[l] = V[l] @ U[l] -- the paper's Eq. (4) as an L-batched GEMM."""
    return _einsum("ltc,lck->ltk", V, U)


def tewmm(V: jax.Array, U: jax.Array) -> jax.Array:
    """Tuple-element-wise multiply (the NNPACK-style baseline): identical
    math to :func:`batched_gemm` but expressed as broadcast-multiply +
    reduction, i.e. Level-1-BLAS-shaped work with low arithmetic intensity.
    Kept as a measured baseline (paper SS4.1)."""
    return jnp.sum(V[:, :, :, None] * U[:, None, :, :], axis=2)


def output_transform(O_hat: jax.Array, m: int, r: int) -> jax.Array:
    """O^ (L, T, K) -> (T, m, m, K) : Y = A^T O^ A."""
    AT, _, _ = _consts(m, r, O_hat.dtype)
    a = m + r - 1
    o = O_hat.reshape(a, a, *O_hat.shape[1:])  # (x, y, T, K)
    return _einsum("ix,xytk,jy->tijk", AT, o, AT)


# ----------------------- filter-gradient pipeline -----------------------
#
# The exact Winograd filter gradient (DESIGN.md SS8): each forward tile
# contributes the valid correlation of its (alpha, alpha) input tile with
# its (m, m) output-gradient tile, producing an (r, r) partial gradient --
# the minimal algorithm F(r, m), whose transforms share the forward's
# evaluation points (same alpha).  The x-side transform is therefore the
# SAME B^T as the forward (``input_transform`` is reused verbatim), and the
# tuple-wise products summed over tiles and batch form an L-batched GEMM
# with the contraction on T:
#
#     dU(L, C, K) = X~(L, C, T) x Gy(L, T, K)     (X~ = V transposed)
#
# -- the dual of the forward GEMM, running on the identical batched-GEMM
# core (kernels/wino_gemm, parallel/executor).


def grad_output_transform(gy_tiles: jax.Array, m: int, r: int) -> jax.Array:
    """(T, m, m, K) -> Gy (L, T, K): the gy-side transform G' gy G'^T.

    G' is the (alpha, m) filter transform of F(r, m): the output gradient
    plays the role of the filter in the gradient convolution.
    """
    _, Gg, _ = _grad_consts(m, r, gy_tiles.dtype)
    g = _einsum("xi,tijk,yj->xytk", Gg, gy_tiles, Gg)
    a = Gg.shape[0]
    return g.reshape(a * a, *g.shape[2:])  # (L, T, K)


def grad_gemm(V: jax.Array, Gy: jax.Array) -> jax.Array:
    """dU[l] = V[l]^T @ Gy[l] -- the gradient GEMM, contraction over T."""
    return _einsum("ltc,ltk->lck", V, Gy)


def filter_grad_inverse(dU: jax.Array, m: int, r: int) -> jax.Array:
    """dU (L, C, K) -> dw (r, r, C, K): A'^T dU A' onto the filter taps."""
    ATg, _, _ = _grad_consts(m, r, dU.dtype)
    a = m + r - 1
    du = dU.reshape(a, a, *dU.shape[1:])  # (x, y, C, K)
    return _einsum("ux,xyck,vy->uvck", ATg, du, ATg)


# ----------------------- adjoint (single-pass) stages -----------------------
#
# The transforms are linear, so the exact VJP of the forward pipeline is its
# transpose, stage by stage: gy runs BACKWARD through the output transform
# (dO^ = A gy A^T), both gradients contract dO^ in the Winograd domain of the
# FORWARD tiling, and the results run backward through the input / filter
# transforms.  This is the dataflow of the single-pass fused backward
# (kernels/wino_fused_bwd.py, DESIGN.md SS8): gy is transformed ONCE and the
# forward V is shared by both gradient GEMMs,
#
#     dV(L, T, C) = dO^(L, T, K) x U^T(L, K, C)     -> dx  (contraction on K)
#     dU(L, C, K) = V^T(L, C, T) x dO^(L, T, K)     -> dw  (contraction on T)
#
# Equivalence with the F(r, m) formulation is the D/D^-1 duality of SS8:
# Gy = (D (.) D) dO^ and A'^T = G^T D^-1, so A'^T dU_Gy A' == G^T dU_adj G
# exactly -- the adjoint epilogue IS the filter-grad inverse with the
# diagonal scaling cancelled.


def output_transform_adjoint(gy_tiles: jax.Array, m: int, r: int) -> jax.Array:
    """(T, m, m, K) -> dO^ (L, T, K): the transpose of ``output_transform``.

    dO^ = A gy A^T with A = (A^T)^T -- gy plays the role O^ played forward.
    """
    AT, _, _ = _consts(m, r, gy_tiles.dtype)
    do = _einsum("ix,tijk,jy->xytk", AT, gy_tiles, AT)
    a = AT.shape[1]
    return do.reshape(a * a, *do.shape[2:])  # (L, T, K)


def input_transform_adjoint(dV: jax.Array, m: int, r: int) -> jax.Array:
    """dV (L, T, C) -> dd (T, a, a, C): the transpose of ``input_transform``.

    dd = B dV B^T; the overlap-add scatter back onto the image
    (``tiles.overlap_add_tiles``) completes dL/dx.
    """
    _, _, BT = _consts(m, r, dV.dtype)
    a = BT.shape[0]
    dv = dV.reshape(a, a, *dV.shape[1:])  # (x, y, T, C)
    return _einsum("xi,xytc,yj->tijc", BT, dv, BT)


def filter_transform_adjoint(dU: jax.Array, m: int, r: int) -> jax.Array:
    """dU (L, C, K) -> dw (r, r, C, K): the transpose of ``filter_transform``.

    dw = G^T dU G == A'^T (D (.) D dU) A' -- identical to
    ``filter_grad_inverse`` on the D-scaled dU (DESIGN.md SS8 duality).
    """
    _, G, _ = _consts(m, r, dU.dtype)
    a = G.shape[0]
    du = dU.reshape(a, a, *dU.shape[1:])  # (x, y, C, K)
    return _einsum("xu,xyck,yv->uvck", G, du, G)


def winograd_backward_reference(
    x: jax.Array,
    w: jax.Array,
    gy: jax.Array,
    *,
    m: int,
    pad: int = 0,
    compute_dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """Single-pass (dx, dw) via the adjoint stages -- the jnp oracle for
    ``kernels.wino_fused_bwd``.  x (N,H,W,C), w (r,r,C,K), gy (N,P,Q,K)."""
    r = w.shape[0]
    in_x, in_w = x.dtype, w.dtype
    x = x.astype(compute_dtype)
    w = w.astype(compute_dtype)
    gy = gy.astype(compute_dtype)
    N, H, W, C = x.shape
    xp, tH, tW, P, Q = tiling.pad_for_tiles(x, m, r, pad)
    d = tiling.flatten_tiles(tiling.extract_tiles(xp, m, r, tH, tW))
    V = input_transform(d, m, r)                         # (L, T, C) -- shared
    U = filter_transform(w, m, r)                        # (L, C, K)
    gy_t = tiling.extract_output_tiles(gy, m, tH, tW)    # (T, m, m, K)
    dO = output_transform_adjoint(gy_t, m, r)            # gy transformed ONCE
    dV = _einsum("ltk,lck->ltc", dO, U)                  # dx GEMM (red = K)
    dU = _einsum("ltc,ltk->lck", V, dO)                  # dw GEMM (red = T)
    dd = input_transform_adjoint(dV, m, r)               # (T, a, a, C)
    dx = tiling.overlap_add_tiles(dd, N, tH, tW, m, r, H, W, pad)
    dw = filter_transform_adjoint(dU, m, r)
    return dx.astype(in_x), dw.astype(in_w)


def winograd_filter_grad_reference(
    x: jax.Array,
    gy: jax.Array,
    *,
    r: int,
    m: int = 4,
    pad: int = 0,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Exact filter gradient dL/dw of ``winograd_conv2d_reference`` via the
    F(r, m) pipeline -- the jnp oracle for the Pallas/sharded dw paths.

    x (N, H, W, C), gy (N, P, Q, K) -> dw (r, r, C, K), matching the VJP of
    ``jax.lax.conv_general_dilated`` w.r.t. the HWIO filter.
    """
    in_dtype = x.dtype
    x = x.astype(compute_dtype)
    gy = gy.astype(compute_dtype)
    N, H, W, C = x.shape
    xp, tH, tW, P, Q = tiling.pad_for_tiles(x, m, r, pad)
    assert gy.shape[1] == P and gy.shape[2] == Q, (gy.shape, P, Q)
    d = tiling.flatten_tiles(tiling.extract_tiles(xp, m, r, tH, tW))
    V = input_transform(d, m, r)                        # (L, T, C): B^T shared
    gy_t = tiling.extract_output_tiles(gy, m, tH, tW)   # (T, m, m, K)
    Gy = grad_output_transform(gy_t, m, r)              # (L, T, K)
    dU = grad_gemm(V, Gy)                               # (L, C, K)
    return filter_grad_inverse(dU, m, r).astype(in_dtype)


# --------------------------- full convolution ---------------------------

def winograd_conv2d_reference(
    x: jax.Array,
    w: jax.Array,
    m: int = 6,
    *,
    pad: int = 0,
    use_tewmm: bool = False,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Full Winograd convolution, pure jnp.  The framework oracle.

    Computes cross-correlation (CNN convention), matching
    ``jax.lax.conv_general_dilated`` with NHWC/HWIO and stride 1.
    """
    r = w.shape[0]
    assert w.shape[0] == w.shape[1], "square filters only"
    in_dtype = x.dtype
    x = x.astype(compute_dtype)
    w = w.astype(compute_dtype)

    N, H, W, C = x.shape
    xp, tH, tW, P, Q = tiling.pad_for_tiles(x, m, r, pad)
    t6 = tiling.extract_tiles(xp, m, r, tH, tW)
    d = tiling.flatten_tiles(t6)                        # (T, a, a, C)
    V = input_transform(d, m, r)                        # (L, T, C)
    U = filter_transform(w, m, r)                       # (L, C, K)
    O_hat = tewmm(V, U) if use_tewmm else batched_gemm(V, U)
    y = output_transform(O_hat, m, r)                   # (T, m, m, K)
    out = tiling.assemble_output(y, N, tH, tW, P, Q)
    return out.astype(in_dtype)


def direct_conv2d(x: jax.Array, w: jax.Array, *, pad: int = 0, stride: int = 1) -> jax.Array:
    """Ground-truth direct convolution (paper's accuracy reference)."""
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def im2col_conv2d(x: jax.Array, w: jax.Array, *, pad: int = 0) -> jax.Array:
    """im2col + single GEMM baseline (classic GEMM convolution)."""
    r = w.shape[0]
    N, H, W, C = x.shape
    xp, tH, tW, P, Q = tiling.pad_for_tiles(x, 1, r, pad)
    t6 = tiling.extract_tiles(xp, 1, r, tH, tW)  # m=1: every output position
    d = t6.reshape(N * tH * tW, r * r * C)
    y = jnp.matmul(d, w.reshape(r * r * C, -1), precision=PRECISION)
    y = y.reshape(N, tH, tW, 1, 1, -1).reshape(N, tH, tW, -1)
    return y[:, :P, :Q, :]


# --------------------------- 1-D convolution ---------------------------

def winograd_conv1d_reference(
    x: jax.Array, w: jax.Array, m: int = 4, *, pad: int = 0
) -> jax.Array:
    """1-D Winograd convolution: x (N, T, C), w (r, C, K) -> (N, P, K).

    Used for the Whisper conv frontend's stride-1 k=3 conv1d.
    """
    r = w.shape[0]
    AT, G, BT = _consts(m, r, jnp.float32)
    in_dtype = x.dtype
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    xp, t, P = tiling.pad_for_tiles_1d(x, m, r, pad)
    d = tiling.extract_tiles_1d(xp, m, r, t)            # (N, t, alpha, C)
    V = _einsum("xi,ntic->xntc", BT, d)                 # (alpha, N, t, C)
    U = _einsum("xi,ick->xck", G, w)                    # (alpha, C, K)
    O_hat = _einsum("xntc,xck->xntk", V, U)
    y = _einsum("mx,xntk->ntmk", AT, O_hat)             # (N, t, m, K)
    y = y.reshape(x.shape[0], t * m, -1)[:, :P, :]
    return y.astype(in_dtype)


def direct_conv1d(x: jax.Array, w: jax.Array, *, pad: int = 0, stride: int = 1) -> jax.Array:
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride,),
        padding=((pad, pad),),
        dimension_numbers=("NWC", "WIO", "NWC"),
    )


# ------------------------ workload bookkeeping ------------------------

def conv_flops_direct(N, H, W, C, K, r, pad=0, stride=1) -> int:
    P = (H + 2 * pad - r) // stride + 1
    Q = (W + 2 * pad - r) // stride + 1
    return 2 * N * P * Q * K * C * r * r


def winograd_stage_flops(N, H, W, C, K, r, m, pad=0) -> dict:
    """Per-stage FLOP counts for the Winograd pipeline (model for SSRoofline)."""
    a = m + r - 1
    L = a * a
    P = H + 2 * pad - r + 1
    Q = W + 2 * pad - r + 1
    tH, tW = -(-P // m), -(-Q // m)
    T = N * tH * tW
    # dense-transform upper bound: 2*a*a*(a+a) muls/adds per tile per channel
    in_tr = 2 * T * C * (a * a * a * 2)
    f_tr = 2 * C * K * (a * r * (r + a))
    gemm = 2 * L * T * C * K
    out_tr = 2 * T * K * (a * m * (a + m))
    return dict(input_transform=in_tr, filter_transform=f_tr, gemm=gemm,
                output_transform=out_tr, total=in_tr + f_tr + gemm + out_tr,
                T=T, L=L, effective_direct=conv_flops_direct(N, H, W, C, K, r, pad))
