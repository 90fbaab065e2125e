"""Pallas kernel: overlapping Winograd input tiles, written as the kernels read them.

The fused kernels consume ``d`` (Tp, alpha^2, Cp): tile t's alpha x alpha
window of the padded image, one (alpha^2, Cp) slab per tile, zero rows
from T up to the block multiple Tp.  This kernel builds it in one pass:
each grid step DMAs a band of whole image rows -- ``bh`` tile rows' worth
plus the r-1 halo rows, overlapping its neighbours' bands through an
element-offset ``BlockSpec`` -- into VMEM, and copies row i of tile
(tr, tc), the alpha contiguous pixels ``x[tr*m + i, tc*m : tc*m + alpha]``,
to rows ``i*alpha ... i*alpha + alpha - 1`` of the tile's slab.  HBM sees
one read of each band and one write of ``d``; the T padding is written by
the grid steps past the last band, so no separate pad pass follows.

Grid: (Tp / rows, C / bc) with rows = bn * bh * tW tiles per step: ``bh``
tile rows of one image, or ``bn`` whole images where one image is small.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.blocking import streamed_vmem_bytes
from .common import compiler_params, default_interpret

# Double-buffered working set one step may take: large enough that a
# step's DMAs dwarf its fixed cost, small enough to leave VMEM to spare.
STEP_VMEM_BYTES = 16 * 2**20


def step_blocks(N: int, tH: int, tW: int, m: int, r: int, Wp: int, C: int,
                elt: int) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(image band block, ``d`` block) of one grid step: the most tiles per
    step whose double-buffered working set fits ``STEP_VMEM_BYTES`` --
    ``bh`` tile rows of one image (bh | tH) or ``bn`` whole images (bn | N)."""
    a = m + r - 1
    bc = 128 if C % 128 == 0 else C

    def blocks(bn, bh):
        return (bn, bh * m + r - 1, Wp, bc), (bn * bh * tW, a * a, bc)

    fits = [(1, bh) for bh in range(1, tH + 1) if tH % bh == 0]
    fits += [(bn, tH) for bn in range(2, N + 1) if N % bn == 0]
    fits = [g for g in fits if streamed_vmem_bytes(elt, *blocks(*g))
            <= STEP_VMEM_BYTES] or [(1, 1)]
    return blocks(*max(fits, key=lambda g: g[0] * g[1]))


def _kernel(x_ref, d_ref, *, m: int, a: int, tW: int, bh: int, groups: int,
            steps: int):
    s = pl.program_id(0)

    @pl.when(s < steps)
    def _():
        def tile_row(g, carry):
            n, b = g // bh, g % bh
            for i in range(a):
                for tc in range(tW):
                    d_ref[g * tW + tc, pl.ds(i * a, a), :] = x_ref[
                        n, b * m + i, pl.ds(tc * m, a), :]
            return carry

        lax.fori_loop(0, groups, tile_row, 0)

    @pl.when(s >= steps)
    def _():
        d_ref[...] = jnp.zeros_like(d_ref)


@functools.partial(jax.jit, static_argnames=("m", "r", "tH", "tW", "Tp",
                                             "interpret"))
def tile_extract(
    xp: jax.Array,
    *,
    m: int,
    r: int,
    tH: int,
    tW: int,
    Tp: int,
    interpret: bool | None = None,
) -> jax.Array:
    """Padded image (N, H', W', C) -> d (Tp, alpha^2, C), rows past
    N*tH*tW zero.  H' >= tH*m + r - 1, W' >= tW*m + r - 1 (as
    ``core.tiles.pad_for_tiles`` gives)."""
    if interpret is None:
        interpret = default_interpret()
    a = m + r - 1
    N, Hp, Wp, C = xp.shape
    assert Hp >= tH * m + r - 1 and Wp >= tW * m + r - 1, (xp.shape, tH, tW)
    T = N * tH * tW
    assert Tp >= T, (Tp, T)
    in_block, out_block = step_blocks(N, tH, tW, m, r, Wp, C, xp.dtype.itemsize)
    bn, band, _, bc = in_block
    bh = (band - r + 1) // m
    rows = out_block[0]
    steps = T // rows
    per_n = tH // bh

    def x_map(s, c):
        s = jnp.minimum(s, steps - 1)   # the zero steps re-read the last band
        n, row = (s * bn, 0) if bn > 1 else (s // per_n, s % per_n * bh * m)
        # a constant 0 where C is one block: Mosaic must see lane alignment
        return n, row, 0, (c * bc if C > bc else 0)

    return pl.pallas_call(
        functools.partial(_kernel, m=m, a=a, tW=tW, bh=bh, groups=bn * bh,
                          steps=steps),
        grid=(pl.cdiv(Tp, rows), C // bc),
        in_specs=[pl.BlockSpec(tuple(map(pl.Element, in_block)), x_map)],
        out_specs=pl.BlockSpec(out_block, lambda s, c: (s, 0, c)),
        out_shape=jax.ShapeDtypeStruct((Tp, a * a, C), xp.dtype),
        compiler_params=compiler_params(streamed_vmem_bytes(
            xp.dtype.itemsize, in_block, out_block)),
        name="tile_extract",
        interpret=interpret,
    )(xp)
