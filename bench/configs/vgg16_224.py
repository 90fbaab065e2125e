"""VGG-16 (configuration D), the plain reference, and its weights.

Simonyan & Zisserman, arXiv:1409.1556, table 1, column D: thirteen 3x3
convolutions with padding 1, each followed by ReLU, in five stages with a
2x2 max-pool after each; then the classifier, fully connected layers of
4096, 4096 and 1000 outputs, ReLU after the first two (dropout is off at
inference).

``init`` makes the weights from a key in the layout that the served
network (``models/vgg16_224.py``) reads; the benchmark hands the same
weights to the program and, made again from the seed, to this reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import refs


def _dense(key, fan_in: int, fan_out: int, gain: float) -> dict:
    kw, kb = jax.random.split(key)
    return {"w": jax.random.normal(kw, (fan_in, fan_out), jnp.float32)
            * (gain / fan_in) ** 0.5,
            "b": 0.01 * jax.random.normal(kb, (fan_out,), jnp.float32)}


def init(key, cfg: dict) -> dict:
    n_convs = sum(n for n, _ in cfg["stages"])
    keys = iter(jax.random.split(key, 2 * n_convs + len(cfg["fc"]) + 1))
    stages, c_in = [], cfg["in_channels"]
    for n, ch in cfg["stages"]:
        convs = []
        for _ in range(n):
            convs.append({
                "w": refs.he_normal(next(keys), 3, c_in, ch),
                "b": 0.01 * jax.random.normal(next(keys), (ch,), jnp.float32)})
            c_in = ch
        stages.append(convs)
    side = cfg["image_size"] // 2 ** len(cfg["stages"])
    widths = [side * side * c_in, *cfg["fc"], cfg["classes"]]
    fc = [_dense(next(keys), a, b, 2.0 if i < len(cfg["fc"]) else 1.0)
          for i, (a, b) in enumerate(zip(widths, widths[1:]))]
    return {"stages": stages, "fc": fc}


def forward(params: dict, x: jax.Array, *, conv, matmul) -> jax.Array:
    for convs in params["stages"]:
        for p in convs:
            x = jax.nn.relu(conv(x, p["w"], 1, 1) + p["b"])
        x = refs.maxpool(x, 2, 2)
    x = x.reshape(x.shape[0], -1)
    for i, p in enumerate(params["fc"]):
        x = matmul(x, p["w"]) + p["b"]
        if i < len(params["fc"]) - 1:
            x = jax.nn.relu(x)
    return x
