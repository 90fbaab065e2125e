"""Pallas kernel: gradient output transform Gy = G' gy G'^T, fused with packing.

The gy-side stage of the exact F(r, m) filter-gradient pipeline (DESIGN.md
SS8): the output gradient plays the role of the filter in the gradient
convolution, so its transform matrix is the (alpha, m) filter transform of
F(r, m).  Same register discipline as the forward transforms (kernels/
input_transform.py): channel-vectorized (bt, bk) vectors, the zero/+-1
structure of G' exploited via unrolled add/mul chains, output written
directly in the (L, T, K) layout the gradient GEMM consumes -- Gy is the
right-hand operand of dU(L, C, K) = X~(L, C, T) x Gy(L, T, K).

Grid: (T / bt, K / bk); each step transforms bt tiles x bk channels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.blocking import streamed_vmem_bytes
from repro.core.transforms import grad_transform_arrays
from .common import apply_matrix, compiler_params, default_interpret


def _kernel(gy_ref, out_ref, *, m: int, r: int, Gg):
    a = m + r - 1
    compute_dtype = jnp.float32
    vecs = [[gy_ref[:, i * m + j, :].astype(compute_dtype) for j in range(m)]
            for i in range(m)]
    # rows: tmp[x][j] = sum_i Gg[x, i] gy[i][j]   (x in [alpha), j in [m))
    tmp = [apply_matrix(Gg, [vecs[i][j] for i in range(m)]) for j in range(m)]
    # cols: Gy[x][y] = sum_j Gg[y, j] tmp[j][x]
    for x in range(a):
        outs = apply_matrix(Gg, [tmp[j][x] for j in range(m)])
        for y in range(a):
            out_ref[x * a + y, :, :] = outs[y].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m", "r", "block_t", "block_k",
                                             "interpret"))
def grad_output_transform(
    gy_flat: jax.Array,
    *,
    m: int,
    r: int,
    block_t: int = 256,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """(T, m^2, K) -> Gy (L, T, K).  T % block_t == 0, K % block_k == 0."""
    if interpret is None:
        interpret = default_interpret()
    a = m + r - 1
    L = a * a
    T, mm, K = gy_flat.shape
    assert mm == m * m, (mm, m)
    assert T % block_t == 0 and K % block_k == 0, (T, K, block_t, block_k)
    _, Gg, _ = grad_transform_arrays(m, r, "float64")

    grid = (T // block_t, K // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, r=r, Gg=Gg),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, mm, block_k), lambda t, k: (t, 0, k))],
        out_specs=pl.BlockSpec((L, block_t, block_k), lambda t, k: (0, t, k)),
        out_shape=jax.ShapeDtypeStruct((L, T, K), gy_flat.dtype),
        compiler_params=compiler_params(streamed_vmem_bytes(
            gy_flat.dtype.itemsize, (block_t, mm, block_k), (L, block_t, block_k))),
        name="grad_output_transform",
        interpret=interpret,
    )(gy_flat)
