"""The paper's benchmark CNNs (Table 1) built on the framework conv op.

VGG-16, ResNet-50 and FusionNet are where the paper's technique is
load-bearing: every stride-1 3x3 convolution routes through
``repro.core.conv2d`` with a selectable algorithm (winograd_fused /
winograd_nonfused / im2col / direct / tewmm), so the paper's library
comparison runs end-to-end through real networks, and the networks are
trainable (the Winograd op carries a custom VJP).

Structures are faithful at the layer-shape level (the paper benchmarks
single layers; we additionally assemble the full networks).  BatchNorm is
replaced by its inference-equivalent scale+shift folded form for ResNet
(per-channel affine) -- the conv benchmarking is unaffected and training
still works (the affine is learned).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.core import conv2d
from repro.core.conv import Algorithm
from repro.core.winograd import PRECISION

from .config import CNNConfig, ConvLayerSpec

Params = dict[str, Any]


def _conv_init(key, r: int, C: int, K: int, dtype=jnp.float32) -> Params:
    fan_in = r * r * C
    w = jax.random.normal(key, (r, r, C, K), jnp.float32) * (2.0 / fan_in) ** 0.5
    return {"w": w.astype(dtype), "b": jnp.zeros((K,), jnp.float32)}


def _affine_init(K: int) -> Params:
    return {"scale": jnp.ones((K,), jnp.float32), "shift": jnp.zeros((K,), jnp.float32)}


def conv_block(p: Params, x: jax.Array, *, stride: int = 1, pad: int = 1,
               algorithm: Algorithm = "auto", act: bool = True) -> jax.Array:
    y = conv2d(x, p["w"], stride=stride, pad=pad, algorithm=algorithm)
    y = y + p["b"].astype(y.dtype)
    if "affine" in p:
        y = y * p["affine"]["scale"].astype(y.dtype) + p["affine"]["shift"].astype(y.dtype)
    return jax.nn.relu(y) if act else y


def maxpool2(x: jax.Array) -> jax.Array:
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def avgpool_global(x: jax.Array) -> jax.Array:
    return jnp.mean(x, axis=(1, 2))


def _head(x: jax.Array, w: jax.Array) -> jax.Array:
    """Classifier logits in f32, at the full f32 precision of the convs."""
    return jnp.matmul(x, w, precision=PRECISION).astype(jnp.float32)


# ------------------------------- VGG-16 -------------------------------

VGG16_PLAN = [  # (n_convs, channels) per stage; maxpool between stages
    (2, 64), (2, 128), (3, 256), (3, 512), (3, 512),
]


def vgg16_init(key, *, in_ch: int = 3, width_mult: float = 1.0,
               n_classes: int = 1000) -> Params:
    keys = jax.random.split(key, 32)
    ki = iter(range(32))
    stages = []
    c_in = in_ch
    for n_convs, ch in VGG16_PLAN:
        ch = max(8, int(ch * width_mult))
        convs = []
        for _ in range(n_convs):
            convs.append(_conv_init(keys[next(ki)], 3, c_in, ch))
            c_in = ch
        stages.append(convs)
    head = jax.random.normal(keys[next(ki)], (c_in, n_classes), jnp.float32) * c_in**-0.5
    return {"stages": stages, "head": head}


def vgg16_forward(params: Params, x: jax.Array, *,
                  algorithm: Algorithm = "auto") -> jax.Array:
    for convs in params["stages"]:
        for p in convs:
            x = conv_block(p, x, pad=1, algorithm=algorithm)
        x = maxpool2(x)
    x = avgpool_global(x)
    return _head(x, params["head"])


# ------------------------------ ResNet-50 ------------------------------

RESNET50_PLAN = [  # (n_blocks, mid_channels) per stage
    (3, 64), (4, 128), (6, 256), (3, 512),
]


def _bottleneck_init(key, c_in: int, mid: int, c_out: int) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "conv1": {**_conv_init(ks[0], 1, c_in, mid), "affine": _affine_init(mid)},
        "conv2": {**_conv_init(ks[1], 3, mid, mid), "affine": _affine_init(mid)},
        "conv3": {**_conv_init(ks[2], 1, mid, c_out), "affine": _affine_init(c_out)},
    }
    if c_in != c_out:
        p["proj"] = {**_conv_init(ks[3], 1, c_in, c_out), "affine": _affine_init(c_out)}
    return p


def _bottleneck(p: Params, x: jax.Array, *, stride: int,
                algorithm: Algorithm) -> jax.Array:
    h = conv_block(p["conv1"], x, stride=1, pad=0, algorithm="direct")
    # the 3x3 stride-1 conv is the Winograd-eligible one
    if stride == 1:
        h = conv_block(p["conv2"], h, stride=1, pad=1, algorithm=algorithm)
    else:
        h = conv_block(p["conv2"], h, stride=stride, pad=1, algorithm="direct")
    h = conv_block(p["conv3"], h, stride=1, pad=0, algorithm="direct", act=False)
    if "proj" in p:
        x = conv_block(p["proj"], x, stride=stride, pad=0, algorithm="direct",
                       act=False)
    elif stride != 1:
        x = x[:, ::stride, ::stride]
    return jax.nn.relu(x + h)


def resnet50_init(key, *, in_ch: int = 3, width_mult: float = 1.0,
                  n_classes: int = 1000) -> Params:
    keys = jax.random.split(key, 64)
    ki = iter(range(64))
    stem = {**_conv_init(keys[next(ki)], 3, in_ch, max(8, int(64 * width_mult))),
            "affine": _affine_init(max(8, int(64 * width_mult)))}
    c_in = max(8, int(64 * width_mult))
    stages = []
    for si, (n_blocks, mid) in enumerate(RESNET50_PLAN):
        mid = max(8, int(mid * width_mult))
        c_out = mid * 4
        blocks = []
        for bi in range(n_blocks):
            blocks.append(_bottleneck_init(keys[next(ki)], c_in, mid, c_out))
            c_in = c_out
        stages.append(blocks)
    head = jax.random.normal(keys[next(ki)], (c_in, n_classes), jnp.float32) * c_in**-0.5
    return {"stem": stem, "stages": stages, "head": head}


def resnet50_forward(params: Params, x: jax.Array, *,
                     algorithm: Algorithm = "auto") -> jax.Array:
    x = conv_block(params["stem"], x, stride=2, pad=1, algorithm="direct")
    x = maxpool2(x)
    for si, blocks in enumerate(params["stages"]):
        for bi, p in enumerate(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            x = _bottleneck(p, x, stride=stride, algorithm=algorithm)
    x = avgpool_global(x)
    return _head(x, params["head"])


# ------------------------------ FusionNet ------------------------------
# Residual encoder-decoder for segmentation (Quan et al.); the paper's
# large-scale benchmark (640x640 inputs, channels 64..1024).

FUSIONNET_CH = [64, 128, 256, 512, 1024]


def _res_block_init(key, ch: int) -> Params:
    ks = jax.random.split(key, 3)
    return {f"conv{i}": _conv_init(ks[i], 3, ch, ch) for i in range(3)}


def _res_block(p: Params, x: jax.Array, algorithm: Algorithm) -> jax.Array:
    h = x
    for i in range(3):
        h = conv_block(p[f"conv{i}"], h, pad=1, algorithm=algorithm,
                       act=(i < 2))
    return jax.nn.relu(x + h)


def fusionnet_init(key, *, in_ch: int = 3, width_mult: float = 1.0,
                   n_classes: int = 1) -> Params:
    keys = jax.random.split(key, 64)
    ki = iter(range(64))
    chs = [max(8, int(c * width_mult)) for c in FUSIONNET_CH]
    enc, dec = [], []
    c_in = in_ch
    for ch in chs:
        enc.append({
            "in": _conv_init(keys[next(ki)], 3, c_in, ch),
            "res": _res_block_init(keys[next(ki)], ch),
        })
        c_in = ch
    for ch in reversed(chs[:-1]):
        dec.append({
            "up": _conv_init(keys[next(ki)], 3, c_in, ch),
            "res": _res_block_init(keys[next(ki)], ch),
        })
        c_in = ch
    out = _conv_init(keys[next(ki)], 3, c_in, n_classes)
    return {"enc": enc, "dec": dec, "out": out}


def fusionnet_forward(params: Params, x: jax.Array, *,
                      algorithm: Algorithm = "auto") -> jax.Array:
    skips = []
    for i, st in enumerate(params["enc"]):
        x = conv_block(st["in"], x, pad=1, algorithm=algorithm)
        x = _res_block(st["res"], x, algorithm)
        if i < len(params["enc"]) - 1:
            skips.append(x)
            x = maxpool2(x)
    for st, skip in zip(params["dec"], reversed(skips)):
        B, H, W, C = x.shape
        x = jax.image.resize(x, (B, H * 2, W * 2, C), "nearest")
        x = conv_block(st["up"], x, pad=1, algorithm=algorithm)
        x = jax.nn.relu(x + skip)
        x = _res_block(st["res"], x, algorithm)
    return conv_block(params["out"], x, pad=1, algorithm="direct", act=False)


# --------------------------- Table 1 layer specs ---------------------------

TABLE1_LAYERS: tuple[ConvLayerSpec, ...] = (
    ConvLayerSpec("VN1.2", 64, 64, 224, 224),
    ConvLayerSpec("VN2.2", 128, 128, 112, 112),
    ConvLayerSpec("VN3.2", 256, 256, 56, 56),
    ConvLayerSpec("VN4.2", 512, 512, 28, 28),
    ConvLayerSpec("VN5.2", 512, 512, 14, 14),
    ConvLayerSpec("FN1.2", 64, 64, 640, 640),
    ConvLayerSpec("FN2.2", 128, 128, 320, 320),
    ConvLayerSpec("FN3.2", 256, 256, 160, 160),
    ConvLayerSpec("FN4.2", 512, 512, 80, 80),
    ConvLayerSpec("FN5.2", 1024, 1024, 40, 40),
    ConvLayerSpec("RN2.1", 64, 64, 112, 112),
    ConvLayerSpec("RN3.1", 128, 128, 56, 56),
    ConvLayerSpec("RN4.1", 256, 256, 28, 28),
    ConvLayerSpec("RN5.1", 512, 512, 14, 14),
)

CNN_CONFIGS = {
    "vgg16": CNNConfig("vgg16", tuple(l for l in TABLE1_LAYERS if l.name.startswith("VN"))),
    "fusionnet": CNNConfig("fusionnet", tuple(l for l in TABLE1_LAYERS if l.name.startswith("FN"))),
    "resnet50": CNNConfig("resnet50", tuple(l for l in TABLE1_LAYERS if l.name.startswith("RN"))),
}

CNN_BUILDERS = {
    "vgg16": (vgg16_init, vgg16_forward),
    "resnet50": (resnet50_init, resnet50_forward),
    "fusionnet": (fusionnet_init, fusionnet_forward),
}


def layer_plans(layers=TABLE1_LAYERS, *, N: int = 1, elt_bytes: int = 4,
                candidates: tuple[int, ...] = (2, 4, 6)):
    """Resolve the ConvPlan for each benchmark layer (the networks'
    Winograd-eligible 3x3 stride-1 convs route through the same cached
    plans at trace time via ``conv2d(algorithm="auto")``).

    Returns [(ConvLayerSpec, ConvPlan), ...]; repeated calls are cache
    hits -- the serving-engine amortization story (DESIGN.md SS5).
    """
    from repro.core.plan import ConvSpec, plan  # deferred: models -> core only

    out = []
    for spec in layers:
        out.append((spec, plan(
            ConvSpec(N=N, H=spec.H, W=spec.W, C=spec.C, K=spec.K, r=spec.r,
                     pad=spec.pad, elt_bytes=elt_bytes),
            candidates=candidates)))
    return out
