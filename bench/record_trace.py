#!/usr/bin/env python3
"""Record a small profiler trace on the chip, for ``tests/test_trace.py``.

    python3 bench/record_trace.py <out_dir>

Traces three calls of one planned Winograd convolution (64 -> 64 channels
at 28x28, batch 2, through the compiled Pallas kernels) inside
``bench.dispatch`` host spans, copies the ``.xplane.pb`` to
``<out_dir>/small.xplane.pb`` and prints every plane and line with a few
event names.  Needs a TPU.
"""

import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import harness  # noqa: E402  (sets the compile cache and sys.path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402

import xtrace  # noqa: E402


def main(out_dir: str) -> int:
    harness.require_accelerator(1)
    from repro.core import conv2d

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 28, 28, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 64, 64), jnp.float32)
    f = jax.jit(lambda x, w: jax.nn.relu(conv2d(x, w, pad=1)))
    f(x, w).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            with harness.span("dispatch"):
                y = f(x, w)
            with harness.span("wait"):
                y.block_until_ready()
        jax.profiler.stop_trace()
        src = xtrace.find_xplane(tmp)
        Path(out_dir).mkdir(parents=True, exist_ok=True)
        dst = Path(out_dir) / "small.xplane.pb"
        shutil.copy(src, dst)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    for plane in ProfileData.from_file(str(dst)).planes:
        for line in plane.lines:
            names = sorted({ev.name for ev in line.events})
            print(f"{plane.name} | {line.name} | {len(names)} names: {names[:12]}")
    print(xtrace.reduce_trace(str(dst)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
