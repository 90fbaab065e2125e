"""VGG-16 (configuration D) as it is served: every convolution through the
program's convolution block (``repro.models.cnn.conv_block``: the planned
``conv2d``, bias and ReLU), the 2x2 max-pool after each stage, then the
three fully connected layers.  The layers are those of the plain reference
beside the configuration (``configs/vgg16_224.py``), in its weight layout.
"""

import jax
import jax.numpy as jnp
from repro.models.cnn import conv_block, maxpool2


def forward(params: dict, x: jax.Array, *, algorithm: str = "auto") -> jax.Array:
    for convs in params["stages"]:
        for p in convs:
            x = conv_block(p, x, pad=1, algorithm=algorithm)
        x = maxpool2(x)
    x = x.reshape(x.shape[0], -1)
    for i, p in enumerate(params["fc"]):
        x = jnp.matmul(x, p["w"]) + p["b"]
        if i < len(params["fc"]) - 1:
            x = jax.nn.relu(x)
    return x
