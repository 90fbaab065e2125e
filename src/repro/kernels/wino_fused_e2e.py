"""Pallas kernel: single-pass Winograd pipeline (transform + GEMM + inverse).

``wino_fused`` fuses the back half of the paper's Algorithm 1 (GEMM +
output transform); the Winograd-domain input V still round-trips HBM
between ``input_transform`` and the GEMM.  This kernel closes the loop: it
consumes the raw extracted-tile blocks d (T, alpha^2, C) directly, so
*neither* V nor O^ ever exists in HBM -- the paper's full single-pipeline
contribution, one grid launch end to end:

  * grid (T/bt, K/bk, C/bc) with C innermost, as in ``wino_fused``;
  * prologue (first K block only): the B^T d B input transform runs on the
    streamed d block and lands in a full-C f32 VMEM V-cache
    (L, bt, C) -- transformed once per tile block, reused by every K block
    (the paper transforms each tile exactly once per pipeline pass);
  * body: L-batched GEMM accumulation from the V-cache into the f32
    (L, bt, bk) scratch across C steps;
  * epilogue (last C step): A^T (.) A inverse transform in-register,
    spatial m x m tiles written out.

The d BlockSpec index map collapses to block (t, 0, 0) for k > 0, so after
the first K block the Pallas pipeline stops streaming d entirely (block
indices that repeat between consecutive steps are not re-fetched): HBM
reads d once per tile block plus a single re-prime block at the k 0->1
transition (none when C fits one block) -- the ``hbm_traffic_e2e`` model
in ``repro.core.blocking``.

VMEM working set (f32): 2*bt*L*bc (d, double-buffered) + 2*L*bc*bk (U)
+ L*bt*C (V-cache) + L*bt*bk (acc) + 2*bt*m^2*bk (out); the blocking
model's "fused_e2e" constraint (``e2e_vmem_bytes``) gates eligibility and
sets the kernel's scoped-VMEM limit.

The V-cache is laid out (C/bc, L, bt, bc): the C-block index selects a
leading, untiled axis, so every cache access is a whole-lane slab and the
kernel compiles for any bc, including C blocks narrower than a lane
(C=64 layers, the C=3 stem padded to 8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blocking import e2e_vmem_bytes
from repro.core.transforms import transform_arrays
from repro.core.winograd import PRECISION
from .common import (apply_matrix, compiler_params,
                     default_interpret)


def _kernel(d_ref, u_ref, y_ref, vcache_ref, acc_ref, *, m: int, r: int,
            AT, BT, n_c: int):
    a = m + r - 1
    L = a * a
    k_idx = pl.program_id(1)
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ---- prologue: B^T d B on the streamed tile block, once per (t, c) ----
    @pl.when(k_idx == 0)
    def _input_transform():
        vecs = [[d_ref[:, i * a + j, :].astype(jnp.float32) for j in range(a)]
                for i in range(a)]
        tmp = [apply_matrix(BT, [vecs[i][j] for i in range(a)]) for j in range(a)]
        for x in range(a):
            outs = apply_matrix(BT, [tmp[j][x] for j in range(a)])
            for y in range(a):
                vcache_ref[c_idx, x * a + y, :, :] = outs[y]

    # ---- L-batched GEMM accumulation, V served from the VMEM cache ----
    for l in range(L):
        acc_ref[l, :, :] += jnp.dot(
            vcache_ref[c_idx, l, :, :],
            u_ref[l, :, :],
            preferred_element_type=jnp.float32,
            precision=PRECISION,
        )

    # ---- epilogue: A^T (.) A inverse transform on the last C step ----
    @pl.when(c_idx == n_c - 1)
    def _epilogue():
        vecs = [[acc_ref[x * a + y, :, :] for y in range(a)] for x in range(a)]
        tmp = [apply_matrix(AT, [vecs[x][y] for x in range(a)]) for y in range(a)]
        for i in range(m):
            outs = apply_matrix(AT, [tmp[y][i] for y in range(a)])
            for j in range(m):
                y_ref[:, i * m + j, :] = outs[j].astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("m", "r", "block_t", "block_k", "block_c", "interpret", "out_dtype"),
)
def wino_fused_e2e(
    d: jax.Array,
    U: jax.Array,
    *,
    m: int,
    r: int,
    block_t: int = 128,
    block_k: int = 128,
    block_c: int = 128,
    interpret: bool | None = None,
    out_dtype=None,
) -> jax.Array:
    """d (T, alpha^2, C) x U (L, C, K) -> spatial tiles y (T, m^2, K).

    Single pass: input transform as GEMM prologue (into a VMEM V-cache),
    inverse transform as GEMM epilogue.  V and O^ never exist in HBM.
    """
    if interpret is None:
        interpret = default_interpret()
    a = m + r - 1
    L = a * a
    T, L_in, C = d.shape
    L2, C2, K = U.shape
    assert L_in == L == L2 and C == C2, (L_in, L, L2, C, C2)
    assert T % block_t == 0 and C % block_c == 0 and K % block_k == 0
    AT, _, BT = transform_arrays(m, r, "float64")
    out_dtype = out_dtype or d.dtype
    n_c = C // block_c

    grid = (T // block_t, K // block_k, n_c)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, r=r, AT=AT, BT=BT, n_c=n_c),
        grid=grid,
        in_specs=[
            # d collapses to block (t, 0, 0) once k > 0: the V-cache serves
            # those steps, so the pipeline re-fetches at most one re-prime
            # block per tile block (repeat indices are not re-streamed).
            pl.BlockSpec((block_t, L, block_c),
                         lambda t, k, c: (t, 0, jnp.where(k == 0, c, 0))),
            pl.BlockSpec((L, block_c, block_k), lambda t, k, c: (0, c, k)),
        ],
        out_specs=pl.BlockSpec((block_t, m * m, block_k), lambda t, k, c: (t, 0, k)),
        out_shape=jax.ShapeDtypeStruct((T, m * m, K), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((n_c, L, block_t, block_c), jnp.float32),
            pltpu.VMEM((L, block_t, block_k), jnp.float32),
        ],
        compiler_params=compiler_params(e2e_vmem_bytes(
            L, m, C, block_t, block_c, block_k, d.dtype.itemsize)),
        name="wino_fused_e2e",
        interpret=interpret,
    )(d, U)
