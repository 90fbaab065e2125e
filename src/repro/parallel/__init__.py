from .executor import execute_gemm, gemm_pspecs, use_mesh  # noqa: F401
from .sharding import (  # noqa: F401
    act_batch_axes,
    axis_size,
    constrain,
    param_pspecs,
    shard_params,
)
