"""Pallas kernel: single-pass fused Winograd backward (dx + dU together).

The backward mirror of ``wino_fused_e2e``.  The two-pass backward pays in
HBM exactly what the forward fusion eliminated: dx re-runs the whole
forward pipeline on gy, and dw round-trips V, Gy, and dU through HBM.  The
adjoint formulation shares every Winograd-domain intermediate between the
two gradients:

    dO^ = A gy A^T                 (gy transformed ONCE, per streamed block)
    dV[l] = dO^[l] @ U[l]^T        (contraction over K)   -> dd = B dV B^T
    dU[l] = V[l]^T @ dO^[l]        (contraction over T)   -> dw = G^T dU G

so one kernel pass over (d, gy, U) emits both dd (spatial dx tiles, ready
for overlap-add) and dU (Winograd-domain filter gradient).  By the
D/D-duality of the transform pair (DESIGN.md SS8), the dU emitted here is
bit-for-bit the F(r, m) filter-gradient formulation's dU.

Grid: (C/bc, T/bt, K/bk) -- C OUTERMOST, K innermost:

  * prologue (first K step): B^T d B runs on the streamed tile block into a
    (L, bt, bc) f32 VMEM V-slice -- the shared V-cache.  d's index map is
    constant across the K sweep, so HBM reads d once per (c, t);
  * every step: A gy A^T on the streamed gy block into a (L, bt, bk) f32
    dO^ scratch, consumed immediately by BOTH contractions;
  * dV accumulates in the dd OUTPUT block itself ((bt, L, bc), resident
    across the K sweep); at the last K step the B (.) B^T inverse transform
    rewrites the block in place -- dV never exists in HBM;
  * dU accumulates in a full-K (L, bc, Kp) output block whose index map is
    constant over the whole (t, k) sweep of one C block -- written back
    exactly once per C block, dU touches HBM once total.

VMEM working set is ``blocking.bwd_fused_vmem_bytes`` (it also sets the
kernel's scoped-VMEM limit); traffic is
``blocking.hbm_traffic_bwd_fused``.  Feasibility (the resident dU block is
the hard constraint) is decided by ``plan.bwd_kernel_blocks``; infeasible
shapes take the two-pass backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blocking import bwd_fused_vmem_bytes
from repro.core.transforms import transform_arrays
from repro.core.winograd import PRECISION
from .common import (compiler_params, default_interpret,
                     transform_2d)


def _kernel(d_ref, gy_ref, u_ref, dd_ref, du_ref, v_ref, do_ref, *,
            m: int, r: int, AT, BT, n_k: int, block_k: int):
    a = m + r - 1
    L = a * a
    t_idx = pl.program_id(1)
    k_idx = pl.program_id(2)
    # K columns of the resident full-K dU block that this step owns.  With
    # one K block that is the whole lane axis (K may be narrower than a
    # lane); with several, block_k is a multiple of 128, which the hint
    # lets Mosaic prove for the dynamic lane offset.
    k_cols = (slice(None) if n_k == 1 else
              pl.ds(pl.multiple_of(k_idx * block_k, block_k), block_k))

    # ---- prologue: B^T d B into the shared V-slice, once per (c, t) ----
    @pl.when(k_idx == 0)
    def _build_v():
        dvecs = [[d_ref[:, i * a + j, :].astype(jnp.float32)
                  for j in range(a)] for i in range(a)]
        v = transform_2d(BT, dvecs)
        for x in range(a):
            for y in range(a):
                v_ref[x * a + y, :, :] = v[x][y]

    # ---- gy -> Winograd domain: dO^ = A gy A^T, once per grid step ----
    gvecs = [[gy_ref[:, i * m + j, :].astype(jnp.float32)
              for j in range(m)] for i in range(m)]
    do = transform_2d(AT.T, gvecs)
    for x in range(a):
        for y in range(a):
            do_ref[x * a + y, :, :] = do[x][y]

    # ---- init the two resident accumulators on their first visit ----
    @pl.when(k_idx == 0)
    def _init_dd():
        dd_ref[...] = jnp.zeros_like(dd_ref)

    @pl.when(t_idx == 0)
    def _init_du():
        du_ref[:, :, k_cols] = jnp.zeros(
            (L, du_ref.shape[1], block_k), jnp.float32)

    # ---- dual GEMMs against the shared V-slice / dO^ ----
    for l in range(L):
        dg = do_ref[l, :, :]                              # (bt, bk)
        # dx side: dV[l] += dO^[l] @ U[l]^T   (contraction over K)
        dd_ref[:, l, :] += jax.lax.dot_general(
            dg, u_ref[l, :, :],
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=PRECISION,
            preferred_element_type=jnp.float32,
        )
        # dw side: dU[l] += V[l]^T @ dO^[l]   (contraction over T)
        du_ref[l, :, k_cols] += jax.lax.dot_general(
            v_ref[l, :, :], dg,
            dimension_numbers=(((0,), (0,)), ((), ())),
            precision=PRECISION,
            preferred_element_type=jnp.float32,
        )

    # ---- epilogue: dd = B dV B^T, rewriting the output block in place ----
    @pl.when(k_idx == n_k - 1)
    def _inverse():
        dvvecs = [[dd_ref[:, x * a + y, :] for y in range(a)]
                  for x in range(a)]
        dd = transform_2d(BT.T, dvvecs)
        for i in range(a):
            for j in range(a):
                dd_ref[:, i * a + j, :] = dd[i][j]


@functools.partial(
    jax.jit,
    static_argnames=("m", "r", "block_t", "block_c", "block_k", "interpret"),
)
def wino_fused_bwd(
    d: jax.Array,
    gy_t: jax.Array,
    U: jax.Array,
    *,
    m: int,
    r: int,
    block_t: int = 64,
    block_c: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """d (T, alpha^2, C) x gy_t (T, m^2, K) x U (L, C, K)
    -> (dd (T, alpha^2, C) f32, dU (L, C, K) f32), one grid launch.

    dd are overlapping spatial gradient tiles (feed ``overlap_add_tiles``);
    dU is the Winograd-domain filter gradient (feed
    ``filter_transform_adjoint``).  All extents must be pre-padded to block
    multiples (zero padding is exact through the bilinear algorithm).
    """
    if interpret is None:
        interpret = default_interpret()
    a = m + r - 1
    L = a * a
    T, L_in, C = d.shape
    T2, M2, K = gy_t.shape
    L2, C2, K2 = U.shape
    assert L_in == L == L2 and T == T2 and C == C2 and K == K2 \
        and M2 == m * m, (d.shape, gy_t.shape, U.shape)
    assert T % block_t == 0 and C % block_c == 0 and K % block_k == 0
    AT, _, BT = transform_arrays(m, r, "float64")
    n_k = K // block_k

    grid = (C // block_c, T // block_t, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, r=r, AT=AT, BT=BT, n_k=n_k,
                          block_k=block_k),
        grid=grid,
        in_specs=[
            # d's index map is constant across the inner K sweep: one HBM
            # fetch per (c, t), served from the V-slice thereafter.
            pl.BlockSpec((block_t, L, block_c), lambda c, t, k: (t, 0, c)),
            pl.BlockSpec((block_t, m * m, block_k),
                         lambda c, t, k: (t, 0, k)),
            pl.BlockSpec((L, block_c, block_k), lambda c, t, k: (0, c, k)),
        ],
        out_specs=[
            # dd: resident across the K sweep (the dV accumulator), written
            # back once per (c, t) after the in-place inverse transform.
            pl.BlockSpec((block_t, L, block_c), lambda c, t, k: (t, 0, c)),
            # dU: full-K block, index map constant over one C block's whole
            # (t, k) sweep -- accumulates in VMEM, one HBM write per C block.
            pl.BlockSpec((L, block_c, K), lambda c, t, k: (0, c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, L, C), jnp.float32),
            jax.ShapeDtypeStruct((L, C, K), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((L, block_t, block_c), jnp.float32),   # V-slice
            pltpu.VMEM((L, block_t, block_k), jnp.float32),   # dO^
        ],
        compiler_params=compiler_params(bwd_fused_vmem_bytes(
            L, m, K, block_t, block_c, block_k, d.dtype.itemsize)),
        name="wino_fused_bwd",
        interpret=interpret,
    )(d, gy_t, U)
