"""Tile extraction / output assembly for the overlap-add (OLA) Winograd scheme.

The paper's transform kernels read overlapping (m+r-1)^2 input tiles straight
from the strided NCHW image using register-reuse schedules (Fig. 2).  The
fused kernels stream dense blocks, so on TPU we realize the same dataflow as
an explicit *tile extraction* pass, after which every kernel sees dense,
non-overlapping blocks.  This is the hardware adaptation recorded in
DESIGN.md SS2/SS8; the r-1 halo duplication factor is (m+r-1)^2 / m^2.

Extraction uses no gather.  Tile ``t`` along an axis covers padded rows
``t*m ... t*m + alpha - 1``: the m rows of block ``t`` followed by the first
``alpha - m`` rows of the next blocks.  So the padded image is reshaped into
m-row blocks and each tile axis is built from ``ceil(alpha / m)`` shifted
slices of whole blocks, concatenated along the within-block axis -- per axis,
then one transpose of major axes.  This is the jnp form, for the reference,
mesh and backward paths; the Pallas pipelines write the same tiles with the
``kernels.tile_extract`` kernel in one pass.

Layout convention is NHWC (TPU-native; channels map to the 128-wide lane
dimension, exactly the role the paper gives its theta-channel vectors).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def num_tiles_1d(out_len: int, m: int) -> int:
    return -(-out_len // m)  # ceil


def conv_out_len(in_len: int, r: int, pad: int) -> int:
    return in_len + 2 * pad - r + 1


def tile_grid(H: int, W: int, m: int, r: int, pad: int) -> tuple[int, int, int, int]:
    """(tH, tW, P, Q): the m x m output tiles covering the (P, Q) conv output."""
    P = conv_out_len(H, r, pad)
    Q = conv_out_len(W, r, pad)
    return num_tiles_1d(P, m), num_tiles_1d(Q, m), P, Q


def padded_len(t: int, m: int, r: int) -> int:
    """Length of a padded axis holding ``t`` tiles as whole m-row blocks:
    the last tile reaches ``ceil(alpha / m) - 1`` blocks past its own."""
    return (t + -(-(m + r - 1) // m) - 1) * m


def pad_for_tiles(x: jax.Array, m: int, r: int, pad: int) -> tuple[jax.Array, int, int, int, int]:
    """Pad NHWC ``x`` so that (H,W) cover a whole number of m x m output tiles,
    each padded axis a whole number of m-row blocks (``padded_len``).

    Returns (padded, tH, tW, P, Q) where (P, Q) is the true conv output size.
    """
    N, H, W, C = x.shape
    tH, tW, P, Q = tile_grid(H, W, m, r, pad)
    x = jnp.pad(
        x,
        ((0, 0), (pad, padded_len(tH, m, r) - H - pad),
         (pad, padded_len(tW, m, r) - W - pad), (0, 0)),
    )
    return x, tH, tW, P, Q


def _tile_axis(x: jax.Array, axis: int, m: int, r: int, t: int) -> jax.Array:
    """Split ``axis`` into (t, alpha): tile i holds entries i*m ... i*m+alpha-1.

    The axis holds at least ``padded_len`` entries, as ``pad_for_tiles``
    gives; it is cut to that and split into m-row blocks, and tile i's alpha
    rows are blocks i, i+1, ... concatenated, the last one cut short.
    """
    alpha = m + r - 1
    nb = -(-alpha // m)
    n = padded_len(t, m, r)
    assert x.shape[axis] >= n, (x.shape, axis, n)
    x = lax.slice_in_dim(x, 0, n, axis=axis)
    x = x.reshape(*x.shape[:axis], t + nb - 1, m, *x.shape[axis + 1:])
    parts = [lax.slice_in_dim(x, j, j + t, axis=axis) for j in range(nb)]
    parts[-1] = lax.slice_in_dim(parts[-1], 0, alpha - (nb - 1) * m, axis=axis + 1)
    return jnp.concatenate(parts, axis=axis + 1)


def extract_tiles(x_padded: jax.Array, m: int, r: int, tH: int, tW: int) -> jax.Array:
    """(N, H', W', C) -> (N, tH, tW, alpha, alpha, C) overlapping tiles.

    Contiguous block slices per axis (module docstring), no gather.
    """
    x = _tile_axis(x_padded, 1, m, r, tH)        # (N, tH, alpha, W', C)
    x = _tile_axis(x, 3, m, r, tW)               # (N, tH, alpha, tW, alpha, C)
    return jnp.transpose(x, (0, 1, 3, 2, 4, 5))  # (N, tH, tW, alpha, alpha, C)


def flatten_tiles(tiles: jax.Array) -> jax.Array:
    """(N, tH, tW, a, a, C) -> (T, a, a, C) with T = N*tH*tW (paper's xi)."""
    N, tH, tW, a, a2, C = tiles.shape
    return tiles.reshape(N * tH * tW, a, a2, C)


def assemble_output(y: jax.Array, N: int, tH: int, tW: int, P: int, Q: int) -> jax.Array:
    """(T, m, m, K) -> (N, P, Q, K): inverse OLA (non-overlapping) + crop."""
    T, m, m2, K = y.shape
    y = y.reshape(N, tH, tW, m, m2, K)
    y = jnp.transpose(y, (0, 1, 3, 2, 4, 5)).reshape(N, tH * m, tW * m2, K)
    return y[:, :P, :Q, :]


def extract_output_tiles(gy: jax.Array, m: int, tH: int, tW: int) -> jax.Array:
    """(N, P, Q, K) -> (T, m, m, K): the exact inverse of ``assemble_output``.

    Output-domain tiles are NON-overlapping m x m blocks; positions beyond
    the true (P, Q) extent are zero-filled, which is numerically free
    through the bilinear algorithm (the backward analogue of the forward's
    edge-tile zero-padding).  Used by the F(r, m) filter-gradient pipeline,
    which pairs each forward input tile d_t with its output-gradient tile.
    """
    N, P, Q, K = gy.shape
    gy = jnp.pad(gy, ((0, 0), (0, tH * m - P), (0, tW * m - Q), (0, 0)))
    gy = gy.reshape(N, tH, m, tW, m, K)
    gy = jnp.transpose(gy, (0, 1, 3, 2, 4, 5))  # (N, tH, tW, m, m, K)
    return gy.reshape(N * tH * tW, m, m, K)


def overlap_add_tiles(dd: jax.Array, N: int, tH: int, tW: int, m: int, r: int,
                      H: int, W: int, pad: int) -> jax.Array:
    """(T, a, a, C) -> (N, H, W, C): the exact adjoint of ``pad_for_tiles``
    + ``extract_tiles`` + ``flatten_tiles``.

    Overlapping tiles ADD back onto the padded image (each padded pixel is
    read by up to ceil(a/m)^2 tiles forward, so its gradient is the sum of
    those tiles' contributions), then the pad border is cropped (adjoint of
    zero-padding).  Realized with ``jax.linear_transpose`` over the
    extraction: the slices and concatenates transpose to pads and adds, no
    scatter -- one definition, provably the transpose of the forward.
    """
    a = m + r - 1
    C = dd.shape[-1]

    def _extract(xp):
        return flatten_tiles(extract_tiles(xp, m, r, tH, tW))

    xp_shape = jax.ShapeDtypeStruct(
        (N, padded_len(tH, m, r), padded_len(tW, m, r), C), dd.dtype)
    (dxp,) = jax.linear_transpose(_extract, xp_shape)(dd.reshape(-1, a, a, C))
    return dxp[:, pad:pad + H, pad:pad + W, :]


# ------------------------------ 1-D variant ------------------------------
# Used by the Whisper conv frontend (k=3, stride 1): the one assigned arch
# where the paper's technique applies natively (DESIGN.md SSArch-applicability).

def pad_for_tiles_1d(x: jax.Array, m: int, r: int, pad: int) -> tuple[jax.Array, int, int]:
    N, Tlen, C = x.shape
    P = conv_out_len(Tlen, r, pad)
    t = num_tiles_1d(P, m)
    x = jnp.pad(x, ((0, 0), (pad, padded_len(t, m, r) - Tlen - pad), (0, 0)))
    return x, t, P


def extract_tiles_1d(x_padded: jax.Array, m: int, r: int, t: int) -> jax.Array:
    return _tile_axis(x_padded, 1, m, r, t)  # (N, t, alpha, C)
