"""Pallas kernel: Winograd filter transform U = G g G^T, fused with packing.

Paper SS3.1.1: the filter transform vectorizes over the K dimension (the
fastest-varying direction of the packed Winograd-domain layout) so stores
stay contiguous.  On TPU that maps to K on lanes: the kernel consumes
(r^2, Cblk, Kblk) blocks and writes (L, Cblk, Kblk) blocks of the
(L, C, K) packed filter tensor -- the layout ``wino_gemm``/``wino_fused``
stream as their stationary-B operand.

In inference mode this runs once per network (paper: "filter transformation
can be omitted" from the steady-state loop); in training it runs per step.

Grid: (C / bc, K / bk).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.blocking import streamed_vmem_bytes
from repro.core.transforms import transform_arrays
from .common import apply_matrix, compiler_params, default_interpret


def _kernel(g_ref, u_ref, *, m: int, r: int, G):
    compute_dtype = jnp.float32
    a = m + r - 1
    vecs = [[g_ref[i * r + j, :, :].astype(compute_dtype) for j in range(r)] for i in range(r)]
    # rows: tmp[x][j] = sum_i G[x, i] g[i][j]   (x in [alpha), j in [r))
    tmp = [apply_matrix(G, [vecs[i][j] for i in range(r)]) for j in range(r)]
    # cols: U[x][y] = sum_j G[y, j] tmp[j][x]
    for x in range(a):
        outs = apply_matrix(G, [tmp[j][x] for j in range(r)])
        for y in range(a):
            u_ref[x * a + y, :, :] = outs[y].astype(u_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m", "r", "block_c", "block_k", "interpret"))
def filter_transform(
    w_flat: jax.Array,
    *,
    m: int,
    r: int,
    block_c: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """(r^2, C, K) -> U (L, C, K)."""
    if interpret is None:
        interpret = default_interpret()
    a = m + r - 1
    L = a * a
    rr, C, K = w_flat.shape
    assert rr == r * r
    assert C % block_c == 0 and K % block_k == 0, (C, K, block_c, block_k)
    _, G, _ = transform_arrays(m, r, "float64")

    grid = (C // block_c, K // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, r=r, G=G),
        grid=grid,
        in_specs=[pl.BlockSpec((rr, block_c, block_k), lambda c, k: (0, c, k))],
        out_specs=pl.BlockSpec((L, block_c, block_k), lambda c, k: (0, c, k)),
        out_shape=jax.ShapeDtypeStruct((L, C, K), w_flat.dtype),
        compiler_params=compiler_params(streamed_vmem_bytes(
            w_flat.dtype.itemsize, (rr, block_c, block_k), (L, block_c, block_k))),
        name="filter_transform",
        interpret=interpret,
    )(w_flat)
