"""Work and bytes of a forward pass, counted from the reference's shapes.

The count is fixed by the configuration: it is read off the convolutions
and matrix products of the plain reference (``configs/<name>.py``) at the
cell's batch and image size, never off the program's plan.  So no choice
of algorithm, tile size or dtype in the program changes it.

* A 3x3 stride-1 convolution counts the multiply-adds of Winograd
  F(6, 3) in its transformed domain: 2 * 64 * C * K * (P * Q / 36) per
  image, with P x Q the output size.
* Every other convolution, and every matrix product (the classifier),
  counts its direct FLOPs: 2 * P * Q * K * r * r * C per image.
* Transforms, pools and elementwise operations count nothing.

For the roofline, each Winograd-eligible convolution's least time is the
larger of its counted FLOPs over the peak and its f32 input, filter and
output bytes over the HBM bandwidth (``least_time``).
"""

from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp

import refs

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}: known {sorted(table)}")
    return table[device_kind]


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _conv_work(eqn) -> dict:
    (N, H, W, C), (r, s, _, K) = (v.aval.shape for v in eqn.invars)
    _, P, Q, _ = eqn.outvars[0].aval.shape
    direct = 2 * N * P * Q * K * r * s * C
    eligible = (r, s) == (3, 3) and tuple(eqn.params["window_strides"]) == (1, 1)
    return {"kind": "conv", "shape": [N, H, W, C, K, r, P, Q],
            "eligible": eligible, "direct": direct,
            "counted": 2 * 64 * C * K * N * P * Q / 36 if eligible else direct,
            "bytes": F32 * (N * H * W * C + r * s * C * K + N * P * Q * K)}


def _dot_work(eqn) -> dict:
    a, b = (v.aval.shape for v in eqn.invars)
    (contract, _), (batch, _) = eqn.params["dimension_numbers"]
    flops = 2 * math.prod(eqn.outvars[0].aval.shape) * math.prod(
        a[d] for d in contract)
    return {"kind": "dot", "eligible": False, "direct": flops,
            "counted": flops, "bytes": F32 * (math.prod(a) + math.prod(b))}


def forward_ops(ref, cfg: dict, batch: int) -> list[dict]:
    """Every convolution and matrix product of one reference forward pass."""
    params = jax.eval_shape(functools.partial(ref.init, cfg=cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    S = cfg["image_size"]
    x = jax.ShapeDtypeStruct((batch, S, S, cfg["in_channels"]), jnp.float32)
    fwd = functools.partial(ref.forward, conv=refs.conv("highest"),
                            matmul=refs.matmul("highest"))
    jaxpr = jax.make_jaxpr(fwd)(params, x).jaxpr
    ops = []
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "conv_general_dilated":
            ops.append(_conv_work(eqn))
        elif eqn.primitive.name == "dot_general":
            ops.append(_dot_work(eqn))
    return ops


def forward_work(ref, cfg: dict, batch: int) -> dict:
    """FLOPs of one forward pass of ``batch`` images: direct and counted."""
    ops = forward_ops(ref, cfg, batch)
    return {"direct_flops": sum(o["direct"] for o in ops),
            "counted_flops": sum(o["counted"] for o in ops),
            "convs": sum(o["kind"] == "conv" for o in ops),
            "eligible_convs": sum(o["eligible"] for o in ops)}


def least_time(ops: list[dict], peak: dict) -> float:
    """Least seconds the chip could take for the Winograd-eligible convs."""
    return sum(max(o["counted"] / peak["bf16_flops_per_s"],
                   o["bytes"] / peak["hbm_bytes_per_s"])
               for o in ops if o["eligible"])
