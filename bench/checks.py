"""The comparisons that decide ``correct``.

Served logits (``logits``): every output that the window produced is
compared with the configuration's plain reference on the same images, run
at the configuration's precision once the program's state is freed.  The
numbers compared:

* ``logits_err``: the widest gap, max |program - reference|, over all
  outputs, as a share of max |reference|;
* ``missing``: outputs that never came or hold a non-finite value.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _gaps(outs, idx, ref):
    """Per output: max |out - ref[idx]|, and whether it is finite."""
    diff = jnp.abs(outs - ref[idx])
    axes = tuple(range(1, outs.ndim))
    return jnp.max(diff, axis=axes), jnp.all(jnp.isfinite(outs), axis=axes)


def reference_logits(cell, images: jax.Array, precision: str,
                     block: int = 32) -> jax.Array:
    """The reference on ``images`` (pool, batch, H, W, C), in blocks of at
    most ``block`` images, with weights made again from the seed."""
    params = cell.weights()
    fwd = cell.reference(precision)
    flat = images.reshape(-1, *images.shape[2:])
    parts = [fwd(params, flat[i:i + block]) for i in range(0, len(flat), block)]
    return jnp.concatenate(parts).reshape(*images.shape[:2], -1)


def logits(cell, outs: list, idx: np.ndarray, images: jax.Array) -> tuple[dict, dict]:
    """Compare every output with the reference on its pool entry.

    ``outs[i]`` is the program's output for ``images[idx[i]]``.  Returns
    (checks, control readings); the control, read only when calibrating,
    is the reference at the next precision down put in the program's
    place."""
    ref = reference_logits(cell, images, cell.cfg["matmul_precision"])
    scale = float(jnp.max(jnp.abs(ref)))
    gap, finite = _gaps(jnp.stack(outs), jnp.asarray(idx), ref)
    gap, finite = np.asarray(gap), np.asarray(finite)
    ok = finite & np.isfinite(gap)
    checks = {"logits_err": float(gap[ok].max() / scale) if ok.any() else float("inf"),
              "missing": float(len(idx) - int(ok.sum()))}
    control = {}
    if cell.control:
        ctl = reference_logits(cell, images, "high")
        control["high"] = {"logits_err": float(jnp.max(jnp.abs(ctl - ref)) / scale),
                           "missing": 0.0}
    return checks, control

