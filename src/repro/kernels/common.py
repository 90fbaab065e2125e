"""Shared helpers for the Winograd Pallas kernels.

``apply_matrix`` is the TPU analogue of the paper's assembly transform
kernels (SS3.1): the small transform matrices (B^T, G, A^T) are unrolled at
trace time into add/mul chains on channel-vectorized registers -- zeros are
skipped, +-1 coefficients become pure add/sub -- exactly the structure
exploitation of the paper's Eq. (6), with the VPU's (8, 128) registers
playing the role of NEON's theta-wide vectors.
"""

from __future__ import annotations

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro.core import blocking


def apply_matrix(mat: np.ndarray, vecs: list[jax.Array]) -> list[jax.Array]:
    """out[i] = sum_j mat[i, j] * vecs[j], unrolled with constant folding."""
    assert mat.shape[1] == len(vecs)
    outs: list[jax.Array] = []
    for i in range(mat.shape[0]):
        acc = None
        for j in range(mat.shape[1]):
            c = float(mat[i, j])
            if c == 0.0:
                continue
            if c == 1.0:
                term = vecs[j]
            elif c == -1.0:
                term = -vecs[j]
            else:
                term = vecs[j] * jnp.asarray(c, dtype=vecs[j].dtype)
            acc = term if acc is None else acc + term
        outs.append(acc if acc is not None else jnp.zeros_like(vecs[0]))
    return outs


def transform_2d(mat: np.ndarray, vecs: list[list[jax.Array]]) -> list[list[jax.Array]]:
    """Apply ``mat`` on both spatial axes of a 2-D nest of vectors.

    vecs[i][j] are (..., lane)-shaped arrays for spatial position (i, j);
    returns out[x][y] = sum_ij mat[x,i] mat[y,j] vecs[i][j].
    """
    n_in = len(vecs)
    # rows first: tmp[x][j] = sum_i mat[x, i] vecs[i][j]
    tmp = [apply_matrix(mat, [vecs[i][j] for i in range(n_in)]) for j in range(len(vecs[0]))]
    # tmp is indexed [j][x]; then columns: out[x][y] = sum_j mat[y, j] tmp[j][x]
    n_out = mat.shape[0]
    out = []
    for x in range(n_out):
        out.append(apply_matrix(mat, [tmp[j][x] for j in range(len(vecs[0]))]))
    return out


def round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def pad_axis_to(x: jax.Array, axis: int, size: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def compiler_params(vmem_bytes: int) -> pltpu.CompilerParams:
    """Mosaic parameters for a kernel whose blocking model puts its VMEM
    working set at ``vmem_bytes``: the scoped-VMEM limit is derived from
    that model (``blocking.vmem_limit_bytes``), never a constant."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=blocking.vmem_limit_bytes(vmem_bytes))


_TPU_KERNEL = re.compile(
    r'%([A-Za-z_]\w*)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"')


def tpu_kernel_calls(hlo_text: str) -> collections.Counter:
    """Compiled Pallas kernels in TPU HLO text, counted by kernel name
    (every ``pallas_call`` here passes its ``name``)."""
    return collections.Counter(_TPU_KERNEL.findall(hlo_text))


def default_interpret() -> bool:
    """Pallas kernels run in interpret mode everywhere except real TPUs."""
    return jax.default_backend() != "tpu"
