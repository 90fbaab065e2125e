"""Pallas kernel: L-batched Winograd-domain GEMM  O^[l] = V[l] @ U[l].

The analogue of the paper's ping-pong GEMM micro-kernel (SS3.2, C4).  The
NEON register double-buffering becomes the Pallas grid pipeline's automatic
VMEM double-buffering; the (alpha=7, eta=8) register-tile search becomes the
(block_t, block_k) MXU-tile choice (multiples of (8, 128), swept by the
blocking model in ``repro.core.blocking``).  Accumulation over the C grid
axis happens in the f32 output block, which stays resident in VMEM across
the innermost grid dimension (the paper keeps the same T_blk x K_blk output
block in L2 across the C loop -- Eq. (10)).

``transpose_lhs=True`` computes O^[l] = V[l]^T @ U[l] for V stored as
(L, red, rows) -- a *transposed-read BlockSpec*: the lhs index map swaps the
row/contraction grid axes so each (red_blk, row_blk) block is fetched
straight from the untransposed layout and contracted on its leading dim by
``dot_general``.  This is what lets the F(r, m) filter-gradient GEMM
dU = X~^T-shaped contraction run without ever materializing the (L, C, T)
transpose of X~ in HBM.

This is the *non-fused* GEMM used by the three-stage baseline; the paper's
contribution C1 (fused epilogue) lives in ``wino_fused.py``.

Grid: (L, rows/bt, K/bk, red/bc), contraction innermost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.blocking import streamed_vmem_bytes
from repro.core.winograd import PRECISION
from .common import compiler_params, default_interpret


def _kernel(v_ref, u_ref, o_ref, *, transpose_lhs: bool):
    c_idx = pl.program_id(3)

    @pl.when(c_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if transpose_lhs:
        # lhs block is (red, rows): contract its LEADING dim against the
        # rhs leading dim -- no in-VMEM transpose materializes either.
        part = jax.lax.dot_general(
            v_ref[0, :, :], u_ref[0, :, :],
            dimension_numbers=(((0,), (0,)), ((), ())),
            precision=PRECISION,
            preferred_element_type=jnp.float32,
        )
    else:
        part = jnp.dot(
            v_ref[0, :, :], u_ref[0, :, :], preferred_element_type=jnp.float32,
            precision=PRECISION,
        )
    o_ref[0, :, :] += part.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "block_k", "block_c", "transpose_lhs",
                     "interpret"),
)
def wino_gemm(
    V: jax.Array,
    U: jax.Array,
    *,
    block_t: int = 256,
    block_k: int = 128,
    block_c: int = 128,
    transpose_lhs: bool = False,
    interpret: bool | None = None,
) -> jax.Array:
    """V (L,T,C) x U (L,C,K) -> O^ (L,T,K) in f32.

    With ``transpose_lhs=True`` the lhs is stored contraction-major,
    V (L,C,T): the result is still (L, T, K) = V^T @ U per l, with T read
    from the lhs trailing dim via the transposed-read BlockSpec.
    """
    if interpret is None:
        interpret = default_interpret()
    if transpose_lhs:
        L, C, T = V.shape
    else:
        L, T, C = V.shape
    L2, C2, K = U.shape
    assert L == L2 and C == C2
    assert T % block_t == 0 and C % block_c == 0 and K % block_k == 0

    if transpose_lhs:
        lhs_spec = pl.BlockSpec((1, block_c, block_t),
                                lambda l, t, k, c: (l, c, t))
    else:
        lhs_spec = pl.BlockSpec((1, block_t, block_c),
                                lambda l, t, k, c: (l, t, c))

    grid = (L, T // block_t, K // block_k, C // block_c)
    return pl.pallas_call(
        functools.partial(_kernel, transpose_lhs=transpose_lhs),
        grid=grid,
        in_specs=[
            lhs_spec,
            pl.BlockSpec((1, block_c, block_k), lambda l, t, k, c: (l, c, k)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_k), lambda l, t, k, c: (l, t, k)),
        out_shape=jax.ShapeDtypeStruct((L, T, K), jnp.float32),
        compiler_params=compiler_params(streamed_vmem_bytes(
            4, (block_t, block_c), (block_c, block_k), (block_t, block_k))),
        name="wino_gemm",
        interpret=interpret,
    )(V, U)
