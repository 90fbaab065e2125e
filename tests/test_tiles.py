"""Tile extraction: the block-slice form against a gather oracle.

``extract_tiles`` builds each tile from contiguous slices of m-row blocks.
The oracle here is the plain index gather (``jnp.take`` along H, then W),
kept in this file so the program itself carries no gather.  The two must
agree bit for bit, and ``overlap_add_tiles`` must stay the exact adjoint
of padding plus extraction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tiles


def take_tiles(xp, m, r, tH, tW):
    """(N, H', W', C) -> (N, tH, tW, a, a, C) by index gathers."""
    a = m + r - 1
    rows = (np.arange(tH)[:, None] * m + np.arange(a)[None, :]).reshape(-1)
    cols = (np.arange(tW)[:, None] * m + np.arange(a)[None, :]).reshape(-1)
    x = jnp.take(xp, jnp.asarray(rows), axis=1)
    x = x.reshape(xp.shape[0], tH, a, *xp.shape[2:])
    x = jnp.take(x, jnp.asarray(cols), axis=3)
    x = x.reshape(xp.shape[0], tH, a, tW, a, xp.shape[-1])
    return jnp.transpose(x, (0, 1, 3, 2, 4, 5))


# (m, r, pad, H, W): every tile size the planner uses, pads 0-2, ragged
# H != W and edge tiles, and m = 1 (the im2col path).
CASES = [
    (6, 3, 1, 14, 14),
    (6, 3, 1, 20, 17),
    (6, 3, 0, 7, 9),
    (4, 3, 1, 13, 9),
    (4, 3, 2, 8, 11),
    (2, 3, 0, 10, 10),
    (2, 3, 1, 7, 6),
    (2, 5, 2, 11, 8),
    (1, 3, 1, 9, 7),
    (1, 5, 2, 6, 5),
]


@pytest.mark.parametrize("m,r,pad,H,W", CASES,
                         ids=[f"F({m},{r})-p{p}-{h}x{w}" for m, r, p, h, w in CASES])
def test_extract_tiles_matches_gather_and_overlap_add_is_its_adjoint(m, r, pad, H, W):
    N, C = 2, 3
    kx, ky = jax.random.split(jax.random.PRNGKey(m * 100 + r * 10 + pad))
    # integers: every sum below is exact in f32
    x = jax.random.randint(kx, (N, H, W, C), -8, 8).astype(jnp.float32)
    xp, tH, tW, P, Q = tiles.pad_for_tiles(x, m, r, pad)
    assert (P, Q) == (H + 2 * pad - r + 1, W + 2 * pad - r + 1)
    assert tH * m >= P and tW * m >= Q

    d = tiles.extract_tiles(xp, m, r, tH, tW)
    ref = take_tiles(xp, m, r, tH, tW)
    assert d.shape == ref.shape == (N, tH, tW, m + r - 1, m + r - 1, C)
    assert jnp.array_equal(d, ref)

    # <extract(pad(x)), y> == <x, overlap_add(y)>
    a = m + r - 1
    y = jax.random.randint(ky, (N * tH * tW, a, a, C), -8, 8).astype(jnp.float32)
    lhs = jnp.sum(tiles.flatten_tiles(d) * y)
    back = tiles.overlap_add_tiles(y, N, tH, tW, m, r, H, W, pad)
    assert back.shape == x.shape
    assert lhs == jnp.sum(x * back)


@pytest.mark.parametrize("m,r", [(6, 3), (4, 3), (2, 5)])
def test_extract_tiles_1d_matches_gather(m, r):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 17, 5))
    xp, t, P = tiles.pad_for_tiles_1d(x, m, r, 1)
    a = m + r - 1
    idx = (np.arange(t)[:, None] * m + np.arange(a)[None, :]).reshape(-1)
    ref = jnp.take(xp, jnp.asarray(idx), axis=1).reshape(2, t, a, 5)
    assert jnp.array_equal(tiles.extract_tiles_1d(xp, m, r, t), ref)


def test_tile_stages_compile_without_gather_or_scatter():
    """The Pallas pipelines' tile stage (``tile_extract``, interpreted on
    the CPU) and the XLA route (references, mesh path, backward) stay
    copies of contiguous rows; the latter's transpose stays pads and adds."""
    from repro.kernels import ops

    m, r, pad, N, H, W, C = 6, 3, 1, 2, 20, 17, 8

    def extract(x):
        xp, tH, tW, _, _ = tiles.pad_for_tiles(x, m, r, pad)
        return tiles.flatten_tiles(tiles.extract_tiles(xp, m, r, tH, tW))

    x = jnp.zeros((N, H, W, C), jnp.float32)
    fwd = jax.jit(extract).lower(x).compile().as_text()
    dd = jnp.zeros(jax.eval_shape(extract, x).shape, jnp.float32)
    bwd = jax.jit(lambda dd: tiles.overlap_add_tiles(
        dd, N, 4, 3, m, r, H, W, pad)).lower(dd).compile().as_text()
    stage = jax.jit(lambda x: ops._tile_matrix(
        x, m, r, pad, 24, C, interpret=True)).lower(x).compile().as_text()
    for hlo in (fwd, bwd, stage):
        assert " gather(" not in hlo and " scatter(" not in hlo


@pytest.mark.parametrize("C,Cp", [(3, 8), (16, 16), (130, 200), (256, 256)],
                         ids=["cpad", "c16", "wide-lanes", "lane-blocks"])
def test_tile_matrix_is_the_padded_gather(C, Cp):
    """``d`` as the Pallas pipelines take it from ``tile_extract``: the
    gather's tiles, zero past T rows and C channels."""
    from repro.kernels import ops

    m, r, pad, N, H, W = 4, 3, 1, 2, 9, 7
    a = m + r - 1
    x = jax.random.normal(jax.random.PRNGKey(C), (N, H, W, C), jnp.float32)
    xp, tH, tW, _, _ = tiles.pad_for_tiles(x, m, r, pad)
    ref = take_tiles(xp, m, r, tH, tW).reshape(-1, a * a, C)
    T = ref.shape[0]
    d = ops._tile_matrix(x, m, r, pad, T + 8, Cp, interpret=True)
    assert d.shape == (T + 8, a * a, Cp)
    assert jnp.array_equal(d[:T, :, :C], ref)
    assert not jnp.any(d[T:]) and not jnp.any(d[:, :, C:])
