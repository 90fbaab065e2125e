"""Pallas kernel: Winograd input transform V = B^T d B, fused with packing.

Paper SS3.1.1 + SS3.1.2 (C2 + C3): the transform is computed on
channel-vectorized registers with the zero/+-1 structure of B^T exploited via
unrolled add/mul chains, and the result is written *directly* in the layout
the GEMM kernel consumes -- packing fused into the transform, no separate
pack pass.

TPU layout: d is the tile-extracted input, flattened to (T, alpha^2, C);
output V is (L, T, C) with C on lanes and T on sublanes, so the GEMM kernel's
(Tblk, Cblk) blocks are contiguous (8, 128)-tiled VMEM loads -- the z-shape
layout's role on this hardware (DESIGN.md SS2).

Grid: (T / bt, C / bc); each step transforms bt tiles x bc channels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.blocking import streamed_vmem_bytes
from repro.core.transforms import transform_arrays
from .common import apply_matrix, compiler_params, default_interpret


def _kernel(d_ref, v_ref, *, m: int, r: int, BT):
    a = m + r - 1
    compute_dtype = jnp.float32
    # load the alpha^2 spatial positions as (bt, bc) vectors
    vecs = [[d_ref[:, i * a + j, :].astype(compute_dtype) for j in range(a)] for i in range(a)]
    # rows: tmp[x][j] = sum_i BT[x, i] d[i][j]
    tmp = [apply_matrix(BT, [vecs[i][j] for i in range(a)]) for j in range(a)]
    # cols: V[x][y] = sum_j BT[y, j] tmp[j][x]
    for x in range(a):
        outs = apply_matrix(BT, [tmp[j][x] for j in range(a)])
        for y in range(a):
            v_ref[x * a + y, :, :] = outs[y].astype(v_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m", "r", "block_t", "block_c", "interpret"))
def input_transform(
    d_flat: jax.Array,
    *,
    m: int,
    r: int,
    block_t: int = 256,
    block_c: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """(T, alpha^2, C) -> V (L, T, C).  T % block_t == 0, C % block_c == 0."""
    if interpret is None:
        interpret = default_interpret()
    a = m + r - 1
    L = a * a
    T, L_in, C = d_flat.shape
    assert L_in == L, (L_in, L)
    assert T % block_t == 0 and C % block_c == 0, (T, C, block_t, block_c)
    _, _, BT = transform_arrays(m, r, "float64")

    grid = (T // block_t, C // block_c)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, r=r, BT=BT),
        grid=grid,
        in_specs=[pl.BlockSpec((block_t, L, block_c), lambda t, c: (t, 0, c))],
        out_specs=pl.BlockSpec((L, block_t, block_c), lambda t, c: (0, t, c)),
        out_shape=jax.ShapeDtypeStruct((L, T, C), d_flat.dtype),
        compiler_params=compiler_params(streamed_vmem_bytes(
            d_flat.dtype.itemsize, (block_t, L, block_c), (L, block_t, block_c))),
        name="input_transform",
        interpret=interpret,
    )(d_flat)
