"""Pallas TPU kernels for the Winograd pipeline.

Validated in interpret mode on the CPU, compiled for a described v5e by
``tests/test_tpu_compile.py``, and run compiled on the chip by
``chip_smoke.py``.

Each kernel module pairs with an oracle in ``ref.py``; ``ops.py`` holds the
jit'd wrappers that compose them into full convolutions.
"""

from .filter_transform import filter_transform  # noqa: F401
from .input_transform import input_transform  # noqa: F401
from .output_transform import output_transform  # noqa: F401
from .wino_fused import wino_fused  # noqa: F401
from .wino_fused_e2e import wino_fused_e2e  # noqa: F401
from .wino_gemm import wino_gemm  # noqa: F401
