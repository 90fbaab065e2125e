"""Pallas kernel: fused Winograd GEMM + output-transform epilogue (paper C1).

The paper's central contribution is coupling the three Winograd stages so
the Winograd-domain tensors live in cache, not main memory (Algorithm 1:
``GEMMOut`` is an L x T_blk x K_blk scratch, inverse-transformed as soon as
the C loop finishes).  On TPU the same structure becomes:

  * grid (T/bt, K/bk, C/bc) with C innermost;
  * an f32 VMEM scratch ``acc`` of shape (L, bt, bk) accumulating the
    L-batched GEMM across C steps (never touching HBM);
  * on the last C step, the A^T (.) A output transform is applied to ``acc``
    in-register and the *spatial-domain* m x m tiles are written out.

Compared to the non-fused pipeline this removes the HBM write+read of
O^ (L x T x K f32) entirely -- for F(6,3), L=64 means the fused kernel
eliminates 64/36 = 1.78x of the *output-side* traffic twice over; the memory
roofline term drops accordingly (EXPERIMENTS.md SSPerf quantifies it from
``cost_analysis``).

VMEM working set (f32): L*bt*bc (V) + L*bc*bk (U) + L*bt*bk (acc)
+ bt*m^2*bk (out), double-buffered on the streamed operands; the blocking
model in ``repro.core.blocking`` picks (bt, bk, bc) under this constraint --
the Eq. (10)/(11) analogue.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.blocking import fused_vmem_bytes
from repro.core.transforms import transform_arrays
from repro.core.winograd import PRECISION
from .common import (apply_matrix, compiler_params,
                     default_interpret)


def _kernel(v_ref, u_ref, y_ref, acc_ref, *, m: int, r: int, AT, n_c: int):
    a = m + r - 1
    L = a * a
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # L-batched GEMM accumulation; unrolled over L so each dot is a clean
    # (bt, bc) x (bc, bk) MXU matmul.
    for l in range(L):
        acc_ref[l, :, :] += jnp.dot(
            v_ref[l, :, :], u_ref[l, :, :], preferred_element_type=jnp.float32,
            precision=PRECISION,
        )

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
def wino_fused(
    V: jax.Array,
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
    """V (L,T,C) x U (L,C,K) -> spatial tiles y (T, m^2, K).

    O^ never exists in HBM: GEMM accumulation and the A^T(.)A inverse
    transform happen in one VMEM-resident pass.
    """
    if interpret is None:
        interpret = default_interpret()
    a = m + r - 1
    L = a * a
    L2, T, C = V.shape
    L3, C2, K = U.shape
    assert L == L2 == L3 and C == C2
    assert T % block_t == 0 and C % block_c == 0 and K % block_k == 0
    AT, _, _ = transform_arrays(m, r, "float64")
    out_dtype = out_dtype or V.dtype
    n_c = C // block_c

    grid = (T // block_t, K // block_k, n_c)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, r=r, AT=AT, n_c=n_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((L, block_t, block_c), lambda t, k, c: (0, t, c)),
            pl.BlockSpec((L, block_c, block_k), lambda t, k, c: (0, c, k)),
        ],
        out_specs=pl.BlockSpec((block_t, m * m, block_k), lambda t, k, c: (t, 0, k)),
        out_shape=jax.ShapeDtypeStruct((T, m * m, K), out_dtype),
        scratch_shapes=[pltpu.VMEM((L, block_t, block_k), jnp.float32)],
        compiler_params=compiler_params(fused_vmem_bytes(
            L, m, block_t, block_c, block_k, V.dtype.itemsize)),
        name="wino_fused",
        interpret=interpret,
    )(V, U)
