"""ResNet-50 v1.5 as it is served: every convolution through the program's
convolution block (``repro.models.cnn.conv_block``: the planned ``conv2d``,
then the folded batch norm and ReLU), so that the planner chooses each
convolution's algorithm; the 3x3/2 max-pool, residual sums, average pool
and classifier around them.  The layers are those of the plain reference
beside the configuration (``configs/resnet50_224.py``), in its layout.
"""

import jax
import jax.numpy as jnp
from repro.models.cnn import conv_block


def forward(params: dict, x: jax.Array, *, algorithm: str = "auto") -> jax.Array:
    stem = params["stem"]
    x = conv_block(stem, x, stride=2, pad=stem["w"].shape[0] // 2,
                   algorithm=algorithm)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), ((0, 0), (1, 1), (1, 1), (0, 0)))
    for si, blocks in enumerate(params["stages"]):
        for bi, p in enumerate(blocks):
            stride = 2 if bi == 0 and si > 0 else 1
            h = conv_block(p["conv1"], x, pad=0, algorithm=algorithm)
            h = conv_block(p["conv2"], h, stride=stride, pad=1,
                           algorithm=algorithm)
            h = conv_block(p["conv3"], h, pad=0, algorithm=algorithm, act=False)
            if "proj" in p:
                x = conv_block(p["proj"], x, stride=stride, pad=0,
                               algorithm=algorithm, act=False)
            x = jax.nn.relu(x + h)
    x = jnp.mean(x, axis=(1, 2))
    return jnp.matmul(x, params["fc"]["w"]) + params["fc"]["b"]
