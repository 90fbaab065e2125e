"""Error-feedback int8 gradient compression (cross-pod sync trick).

At 1000+-node scale the pod-crossing gradient all-reduce is the scarcest
bandwidth (DCN, not ICI).  We compress gradients to int8 with a per-leaf
scale before that reduction and carry the quantization residual into the
next step (error feedback, Seide et al. 2014) so the bias vanishes over
time.

Under single-controller pjit we cannot annotate *which* all-reduce carries
the compressed payload, so the framework applies compression as a grad
transform at the microbatch-accumulation boundary: grads are quantized,
dequantized, and the residual is carried in a state tree.  On a real
deployment the quantized tensor is what crosses the pod axis
(shard_map + ppermute ring over "pod"); ``ring_allreduce_int8`` below is
that shard_map building block, exercised by tests on a host mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quantize(x: jax.Array):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array):
    return q.astype(jnp.float32) * scale


def ef_init(params):
    """Residual buffers, one per leaf (fp32)."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


def ef_compress_grads(grads, residual):
    """Quantize grads+residual to int8, return (dequantized, new_residual)."""
    def leaf(g, r):
        x = g.astype(jnp.float32) + r
        q, s = _quantize(x)
        dq = _dequantize(q, s)
        return dq.astype(g.dtype), x - dq

    out = jax.tree_util.tree_map(leaf, grads, residual)
    deq = jax.tree_util.tree_map(lambda o: o[0], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
    res = jax.tree_util.tree_map(lambda o: o[1], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
    return deq, res


def ring_allreduce_int8(x: jax.Array, mesh, axis: str = "pod"):
    """shard_map int8 ring all-reduce over one mesh axis.

    Payload crosses the axis as int8 + fp32 scale (a 4x byte saving vs f32);
    each hop dequantizes, accumulates in fp32 and re-quantizes.  Exact for
    axis_size=1; quantization error otherwise (bounded by error feedback at
    the caller).
    """
    axis_size = mesh.shape[axis]

    def body(xs):
        q, s = _quantize(xs.astype(jnp.float32))
        acc = _dequantize(q, s)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        for _ in range(axis_size - 1):
            q = jax.lax.ppermute(q, axis, perm)
            s = jax.lax.ppermute(s, axis, perm)
            acc = acc + _dequantize(q, s)
        return acc.astype(xs.dtype)

    spec = P(*(axis if i == 0 else None for i in range(max(x.ndim, 1))))
    del spec  # payload is replicated over `axis`; reduce in place
    return jax.shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    )(x)
