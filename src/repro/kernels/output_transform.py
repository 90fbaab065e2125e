"""Pallas kernel: Winograd output transform Y = A^T O^ A (standalone).

Used by the non-fused three-stage baseline (the paper's "NCNN-like"
configuration): reads the HBM-resident O^ (L, T, K) produced by
``wino_gemm`` and writes spatial-domain m x m tiles.  The fused pipeline
(``wino_fused``) performs this transform as a GEMM epilogue while O^ is
still in VMEM, which is exactly the paper's C1 saving.

Grid: (T / bt, K / bk).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.blocking import streamed_vmem_bytes
from repro.core.transforms import transform_arrays
from .common import apply_matrix, compiler_params, default_interpret


def _kernel(o_ref, y_ref, *, m: int, r: int, AT):
    a = m + r - 1
    compute_dtype = jnp.float32
    vecs = [[o_ref[x * a + y, :, :].astype(compute_dtype) for y in range(a)] for x in range(a)]
    # rows: tmp[i][y] = sum_x AT[i, x] O[x][y]
    tmp = [apply_matrix(AT, [vecs[x][y] for x in range(a)]) for y in range(a)]
    # cols: Y[i][j] = sum_y AT[j, y] tmp[y][i]
    for i in range(m):
        outs = apply_matrix(AT, [tmp[y][i] for y in range(a)])
        for j in range(m):
            y_ref[:, i * m + j, :] = outs[j].astype(y_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("m", "r", "block_t", "block_k", "interpret", "out_dtype")
)
def output_transform(
    O_hat: jax.Array,
    *,
    m: int,
    r: int,
    block_t: int = 256,
    block_k: int = 128,
    interpret: bool | None = None,
    out_dtype=None,
) -> jax.Array:
    """O^ (L, T, K) -> y (T, m^2, K)."""
    if interpret is None:
        interpret = default_interpret()
    a = m + r - 1
    L = a * a
    L2, T, K = O_hat.shape
    assert L == L2
    assert T % block_t == 0 and K % block_k == 0
    AT, _, _ = transform_arrays(m, r, "float64")
    out_dtype = out_dtype or O_hat.dtype

    grid = (T // block_t, K // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, r=r, AT=AT),
        grid=grid,
        in_specs=[pl.BlockSpec((L, block_t, block_k), lambda t, k: (0, t, k))],
        out_specs=pl.BlockSpec((block_t, m * m, block_k), lambda t, k: (t, 0, k)),
        out_shape=jax.ShapeDtypeStruct((T, m * m, K), out_dtype),
        compiler_params=compiler_params(streamed_vmem_bytes(
            max(O_hat.dtype.itemsize, jnp.dtype(out_dtype).itemsize),
            (L, block_t, block_k), (block_t, m * m, block_k))),
        name="output_transform",
        interpret=interpret,
    )(O_hat)
