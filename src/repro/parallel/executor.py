"""Plan-driven shard_map execution of the Winograd-domain batched GEMM.

``strategy.py`` models the paper's three-mode parallel strategy (C6) and
``core.plan`` caches the per-layer-shape choice; this module is where a
chosen mode actually RUNS.  The unit of execution is the batched GEMM at
the heart of every Winograd pipeline,

    O^(L, T, K) = V(L, T, C) x U(L, C, K),

and each mode is a (in_specs, out_specs, reduction) triple for a
``shard_map`` over the ("data", "model") mesh:

  mode     V spec                U spec              out spec / collective
  "data"   P(-, (data,model), -) P()  (replicated)   P(-, (data,model), -)
           only-T: tiles over every device, U broadcast once, zero
           per-step collectives -- shallow layers, huge T.
  "2d"     P(-, data, -)         P(-, -, model)      P(-, data, model)
           T over the data axis x K over the model axis; no in-kernel
           collective (each rank owns a (T/dp, K/tp) output block).
  "model"  P(-, -, data)         P(-, data, model)   P(-, -, model),
           only-C&K: the contraction axis C over "data" and K over
           "model"; every rank computes a partial (T, K/tp) product and
           the partials are ``psum``-ed over "data" -- deep layers where
           T is tiny and C*K dominates.

Ragged extents (the paper's edge-case tiles) are handled exactly like the
kernel layer handles them: zero-pad T/C/K up to the mesh-axis multiple
before the shard_map and crop after -- zero rows/columns are exact
pass-throughs of the bilinear algorithm, and zero C-slices contribute
nothing to the psum.

The three modes are instances of a general ``GemmAssignment`` (row /
contraction / column axis placement); the backward pass executes two more
GEMMs per conv -- dx contracting K, dw contracting T (the F(r, m) filter
gradient) -- whose assignments are the forward mode's with the roles
permuted (``grad_assignments``, DESIGN.md SS8): every tensor keeps its
forward placement, and the psum moves to whichever role holds the
contracted axis.

``use_mesh`` installs an ambient (mesh, mode) so call sites that cannot
thread a mesh argument (the CNN forwards under ``serve.ConvServeEngine``)
still route through the executor: ``core.conv.conv2d`` checks
``active_mesh()`` when no explicit mesh is passed.

The local per-shard compute is the XLA batched matmul with f32
accumulation (matching ``kernels/wino_gemm``'s contract).  On a real TPU
mesh the local matmul lowers to the MXU; swapping in the Pallas fused
kernel per shard is a one-line change via ``local_fn`` and is measured
separately (the kernel-level story lives in kernels/, the distribution
story here -- DESIGN.md SS6).
"""

from __future__ import annotations

import contextlib
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.blocking import round_up as _round_up
from repro.core.winograd import PRECISION

from .strategy import MODES

DATA_AXIS = "data"
MODEL_AXIS = "model"

AxisSpec = "str | tuple[str, ...] | None"


class GemmAssignment(NamedTuple):
    """Mesh-axis placement of the three batched-GEMM roles.

    ``execute_gemm`` computes out(L, row, col) = V(L, row, red) x
    U(L, red, col); each field names the mesh axis (or axis tuple) that
    role is sharded over, or None for unsharded.  A sharded ``red``
    (contraction) axis means every rank computes a partial product and the
    partials are psum-ed over it.  The three canonical forward modes are
    assignments too (``MODE_ASSIGNMENTS``); the backward GEMMs of the
    gradient pipelines permute them (``grad_assignments`` -- the
    "backward-aware PartitionSpecs" of DESIGN.md SS8).
    """

    row: AxisSpec = None
    red: AxisSpec = None
    col: AxisSpec = None


#: forward-mode placement of (T, C, K) -- T is the GEMM row, C the
#: contraction, K the column (DESIGN.md SS6 table).
MODE_ASSIGNMENTS: dict[str, GemmAssignment] = {
    "data": GemmAssignment(row=(DATA_AXIS, MODEL_AXIS), red=None, col=None),
    "2d": GemmAssignment(row=DATA_AXIS, red=None, col=MODEL_AXIS),
    "model": GemmAssignment(row=None, red=DATA_AXIS, col=MODEL_AXIS),
}


def grad_assignments(mode: str) -> tuple[GemmAssignment, GemmAssignment]:
    """(dx, dw) GEMM assignments dual to a forward mode.

    Every tensor keeps its forward placement in the backward pass; only
    the GEMM roles permute:

      dx:  dV(L, T, C) = dO(L, T, K) x U^T(L, K, C)   (contraction on K)
      dw:  dU(L, C, K) = V^T(L, C, T) x Gy(L, T, K)   (contraction on T)

    so e.g. forward "2d" (T over data x K over model) yields a dw GEMM
    that is exactly the forward "model" spec-triple (contract over "data",
    psum the partials) and a dx GEMM that is its transpose (contract over
    "model") -- the "model"-mode psum changes axis in the gradient.
    """
    fwd = MODE_ASSIGNMENTS[mode]
    t_ax, c_ax, k_ax = fwd.row, fwd.red, fwd.col
    dx = GemmAssignment(row=t_ax, red=k_ax, col=c_ax)
    dw = GemmAssignment(row=c_ax, red=t_ax, col=k_ax)
    return dx, dw


def _pad_axis(x: jax.Array, axis: int, size: int) -> jax.Array:
    # same zero-pad as kernels/common.pad_axis_to, local to keep the
    # parallel layer off the kernels package
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def gemm_pspecs(mode: "str | GemmAssignment") -> tuple[P, P, P, AxisSpec]:
    """(V_spec, U_spec, out_spec, psum_axis) for a mode or assignment."""
    if mode == "data":
        t = (DATA_AXIS, MODEL_AXIS)
        return P(None, t, None), P(), P(None, t, None), None
    if mode == "2d":
        return (P(None, DATA_AXIS, None), P(None, None, MODEL_AXIS),
                P(None, DATA_AXIS, MODEL_AXIS), None)
    if mode == "model":
        return (P(None, None, DATA_AXIS), P(None, DATA_AXIS, MODEL_AXIS),
                P(None, None, MODEL_AXIS), DATA_AXIS)
    if isinstance(mode, GemmAssignment):
        return (P(None, mode.row, mode.red), P(None, mode.red, mode.col),
                P(None, mode.row, mode.col), mode.red)
    raise ValueError(f"unknown parallel mode {mode!r}; expected one of "
                     f"{MODES} or a GemmAssignment")


def _axis_factor(spec: AxisSpec, mesh) -> int:
    """Number of shards a spec entry splits its array axis into."""
    if spec is None:
        return 1
    if isinstance(spec, str):
        return mesh.shape[spec]
    n = 1
    for a in spec:
        n *= mesh.shape[a]
    return n


def _padded_dims(mode, T: int, C: int, K: int, mesh):
    """Global extents padded so every sharded axis divides its mesh axes."""
    if isinstance(mode, str):
        mode = MODE_ASSIGNMENTS[mode]
    return (
        _round_up(T, _axis_factor(mode.row, mesh)),
        _round_up(C, _axis_factor(mode.red, mesh)),
        _round_up(K, _axis_factor(mode.col, mesh)),
    )


def _local_matmul(v, u):
    return jnp.einsum("ltc,lck->ltk", v, u,
                      precision=PRECISION,
                      preferred_element_type=jnp.float32)


def execute_gemm(
    V: jax.Array,
    U: jax.Array,
    *,
    mode: str,
    mesh,
    local_fn=_local_matmul,
) -> jax.Array:
    """V (L,T,C) x U (L,C,K) -> O^ (L,T,K) in f32, sharded per ``mode``.

    ``mode`` is a canonical forward-mode name or a ``GemmAssignment`` (the
    backward GEMMs of the gradient pipelines pass the latter; the array
    roles are then (L, row, red) x (L, red, col) -> (L, row, col)).
    Jit-traceable (the pad/crop and the shard_map are all traced ops), so
    it composes with the serving engine's per-signature jit cache.
    """
    L, T, C = V.shape
    L2, C2, K = U.shape
    assert L == L2 and C == C2, (V.shape, U.shape)
    Tp, Cp, Kp = _padded_dims(mode, T, C, K, mesh)
    V = _pad_axis(_pad_axis(V, 1, Tp), 2, Cp)
    U = _pad_axis(_pad_axis(U, 1, Cp), 2, Kp)

    v_spec, u_spec, out_spec, psum_axis = gemm_pspecs(mode)

    def local(v, u):
        o = local_fn(v, u)
        if psum_axis is not None:
            o = jax.lax.psum(o, psum_axis)
        return o

    out = jax.shard_map(local, mesh=mesh, in_specs=(v_spec, u_spec),
                        out_specs=out_spec, check_vma=False)(V, U)
    return out[:, :T, :K]


# ------------------------- ambient executor mesh -------------------------
#
# ``conv2d(mesh=...)`` is the explicit route; ``use_mesh`` is the implicit
# one for code that calls conv2d deep inside a model forward (the CNN
# serving engine).  Thread-local so concurrent engines on different meshes
# do not interfere; read at TRACE time, so a jit cache compiled under
# ``use_mesh`` keeps its sharded form forever.

_ambient = threading.local()


def active_mesh():
    """(mesh, mode_override) installed by ``use_mesh``, or (None, None)."""
    return (getattr(_ambient, "mesh", None), getattr(_ambient, "mode", None))


@contextlib.contextmanager
def use_mesh(mesh, mode: str | None = None):
    """Route every in-scope ``conv2d`` through the executor on ``mesh``.

    ``mode=None`` leaves the per-layer choice to ``ConvPlan.parallel_mode``
    (the single decision point); passing a mode forces it everywhere --
    benchmarks use that to sweep all three.
    """
    prev = active_mesh()
    _ambient.mesh, _ambient.mode = mesh, mode
    try:
        yield mesh
    finally:
        _ambient.mesh, _ambient.mode = prev
