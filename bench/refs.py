"""Plain reference operations, independent of the program under test.

Nothing here imports the program.  The configurations' references
(``configs/<name>.py``) build their forward passes from these operations.
Two precisions exist:

* ``"highest"``: every f32 product at full precision (the reference);
* ``"high"``: the three-pass bfloat16 product (the TPU's ``HIGH``): each
  operand is split into a head and a tail of 8 significant bits each, both
  exact in bfloat16, and the product is ``hi*hi + hi*lo + lo*hi``,
  accumulated in f32.  It is written out here rather than asked of XLA,
  so that it means the same on every backend: this is the control that the
  correctness limits must reject.  The split rounds away the low 16 bits
  of the f32 word by integer arithmetic, where a round trip through
  bfloat16 would do: XLA may drop such a round trip as excess precision,
  in one place and not another.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "high")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (``PRNGKey`` keeps 32 bits only)."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def _head(a: jax.Array) -> jax.Array:
    """``a`` rounded to its sign, exponent and first 7 stored mantissa
    bits (to nearest, ties away from zero): a bfloat16 value, in f32."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + jnp.uint32(0x8000)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _split(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    hi = _head(a)
    return hi, _head(a - hi)


def _three_pass(op, a, b):
    """``op(a, b)`` for a bilinear ``op``, in three bfloat16 passes."""
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    return op(a_hi, b_hi) + (op(a_hi, b_lo) + op(a_lo, b_hi))


def _conv(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _matmul(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def conv(precision: str):
    """(x, w, stride, pad) -> NHWC cross-correlation at ``precision``."""
    if precision == "highest":
        return _conv
    if precision == "high":
        return lambda x, w, stride, pad: _three_pass(
            functools.partial(_conv, stride=stride, pad=pad), x, w)
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def matmul(precision: str):
    if precision == "highest":
        return _matmul
    if precision == "high":
        return functools.partial(_three_pass, _matmul)
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def maxpool(x, size: int, stride: int, pad: int = 0):
    """Max over ``size`` x ``size`` windows; the padding never wins."""
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, size, size, 1), (1, stride, stride, 1),
        ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def he_normal(key, r: int, C: int, K: int) -> jax.Array:
    return jax.random.normal(key, (r, r, C, K), jnp.float32) * (2.0 / (r * r * C)) ** 0.5

