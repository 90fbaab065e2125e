"""ResNet-50 v1.5, the plain reference, and its weights.

He et al., arXiv:1512.03385, table 1, 50-layer column, with the stride of
v1.5 (NVIDIA DeepLearningExamples, ``resnet50v1.5``): a 7x7/2 convolution
of 64 channels, a 3x3/2 max-pool with padding 1, then bottleneck blocks
1x1 -> 3x3 -> 1x1 with 3, 4, 6 and 3 blocks of 64, 128, 256 and 512
middle channels (four times as many out), the stride of each stage's
first block on its 3x3 convolution, a 1x1 projection on each stage's
first block, global average pool and one fully connected layer of 1000.
Each convolution has no bias and is followed by batch normalisation in
its inference form, a per-channel scale and shift.

``init`` makes the weights from a key in the layout that the served
network (``models/resnet50_224.py``) reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import refs


def _conv_bn(key, r: int, C: int, K: int, gamma: float = 1.0) -> dict:
    kw, ks, kt = jax.random.split(key, 3)
    return {"w": refs.he_normal(kw, r, C, K),
            "b": jnp.zeros((K,), jnp.float32),
            "affine": {
                "scale": gamma * (1 + 0.1 * jax.random.normal(ks, (K,), jnp.float32)),
                "shift": 0.01 * jax.random.normal(kt, (K,), jnp.float32)}}


def init(key, cfg: dict) -> dict:
    n_blocks = sum(n for n, _ in cfg["stages"])
    keys = iter(jax.random.split(key, 4 * n_blocks + 3))
    stem = _conv_bn(next(keys), cfg["stem_kernel"], cfg["in_channels"],
                    cfg["stem_channels"])
    c_in, stages = cfg["stem_channels"], []
    for n, mid in cfg["stages"]:
        c_out, blocks = mid * cfg["expansion"], []
        for _ in range(n):
            # The last affine of each residual branch starts at half scale,
            # so that the sum over 16 blocks keeps its activations in range.
            block = {"conv1": _conv_bn(next(keys), 1, c_in, mid),
                     "conv2": _conv_bn(next(keys), 3, mid, mid),
                     "conv3": _conv_bn(next(keys), 1, mid, c_out, gamma=0.5)}
            proj_key = next(keys)
            if c_in != c_out:
                block["proj"] = _conv_bn(proj_key, 1, c_in, c_out)
            blocks.append(block)
            c_in = c_out
        stages.append(blocks)
    kw, kb = jax.random.split(next(keys))
    fc = {"w": jax.random.normal(kw, (c_in, cfg["classes"]), jnp.float32)
          * c_in ** -0.5,
          "b": 0.01 * jax.random.normal(kb, (cfg["classes"],), jnp.float32)}
    return {"stem": stem, "stages": stages, "fc": fc}


def _conv_bn_apply(p, x, stride, pad, conv, act=True):
    y = conv(x, p["w"], stride, pad) + p["b"]
    y = y * p["affine"]["scale"] + p["affine"]["shift"]
    return jax.nn.relu(y) if act else y


def forward(params: dict, x: jax.Array, *, conv, matmul) -> jax.Array:
    stem = params["stem"]
    x = _conv_bn_apply(stem, x, 2, stem["w"].shape[0] // 2, conv)
    x = refs.maxpool(x, 3, 2, 1)
    for si, blocks in enumerate(params["stages"]):
        for bi, p in enumerate(blocks):
            stride = 2 if bi == 0 and si > 0 else 1
            h = _conv_bn_apply(p["conv1"], x, 1, 0, conv)
            h = _conv_bn_apply(p["conv2"], h, stride, 1, conv)
            h = _conv_bn_apply(p["conv3"], h, 1, 0, conv, act=False)
            if "proj" in p:
                x = _conv_bn_apply(p["proj"], x, stride, 0, conv, act=False)
            x = jax.nn.relu(x + h)
    return matmul(jnp.mean(x, axis=(1, 2)), params["fc"]["w"]) + params["fc"]["b"]
