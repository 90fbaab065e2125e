"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode accepts what the chip's compiler refuses: a working set over
the kernel's scoped-VMEM limit, a dynamic lane slice it cannot prove
aligned to 128.  These tests run the TPU compiler on the Winograd
pipelines at Table-1 widths, with ``interpret=False``, for one chip of a
described (not attached) ``v5e:2x2`` -- no chip is needed -- and check
that each kernel is in the compiled HLO.  They include the C=64, F(6,3)
layer whose V-cache slice used to be refused.

The topology is described inside the ``topo`` fixture (never at import or
collection): only the worker that runs this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.plan import ConvSpec, plan
from repro.kernels import ops
from repro.kernels.common import tpu_kernel_calls
from repro.models.cnn import TABLE1_LAYERS

LAYERS = {spec.name: spec for spec in TABLE1_LAYERS}


@pytest.fixture(scope="module")
def topo():
    # libtpu writes its logs under /tmp unless told otherwise, and compiles
    # for a described chip must not go to the persistent cache (they cannot
    # be read back without a chip).
    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    saved_cache = jax.config.jax_enable_compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        from jax.experimental import topologies

        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to rehearse
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _layer(name, one_chip, N=1):
    spec = LAYERS[name]
    p = plan(ConvSpec(N=N, H=spec.H, W=spec.W, C=spec.C, K=spec.K,
                      r=spec.r, pad=spec.pad))

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    x = sds((N, spec.H, spec.W, spec.C))
    w = sds((spec.r, spec.r, spec.C, spec.K))
    gy = sds((N, spec.H, spec.W, spec.K))
    return spec, p, x, w, gy


@pytest.mark.parametrize("name,m", [("VN1.2", 6), ("VN3.2", 4),
                                    ("FN5.2", 2)])
def test_fused_e2e_compiles_for_v5e(one_chip, name, m):
    spec, p, x, w, _ = _layer(name, one_chip)
    assert (p.pipeline, p.m) == ("fused_e2e", m), (p.pipeline, p.m)
    hlo = ops.conv2d_pallas.lower(
        x, w, m=p.m, pad=spec.pad, pipeline="fused_e2e",
        interpret=False).compile().as_text()
    calls = tpu_kernel_calls(hlo)
    assert calls["wino_fused_e2e"] == 1 and calls["filter_transform"] == 1, \
        calls
    assert calls["tile_extract"] == 1 and " gather(" not in hlo, calls


@pytest.mark.parametrize("N,H,W,C,m", [(2, 20, 17, 64, 6), (2, 224, 224, 3, 4),
                                       (2, 14, 14, 256, 4), (2, 7, 7, 512, 2)])
def test_tile_stage_compiles_without_gather_for_v5e(one_chip, N, H, W, C, m):
    """The input tiles come from the ``tile_extract`` kernel, padded to the
    block multiples by the kernel itself: no gather, no pad of ``d``."""
    x = jax.ShapeDtypeStruct((N, H, W, C), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3, C, 64), jnp.float32, sharding=one_chip)
    hlo = ops.conv2d_pallas.lower(
        x, w, m=m, pad=1, pipeline="fused_e2e",
        interpret=False).compile().as_text()
    assert " gather(" not in hlo
    assert tpu_kernel_calls(hlo)["tile_extract"] == 1
    # d goes from the extraction kernel straight into the Winograd kernel
    assert re.search(r"%wino_fused_e2e[.\d]* = [^\n]*"
                     r"custom-call\(%tile_extract[.\d]*,", hlo), hlo[-3000:]


@pytest.mark.parametrize("pipeline,kernels", [
    ("fused", ("input_transform", "wino_fused")),
    ("nonfused", ("input_transform", "wino_gemm", "output_transform")),
])
def test_two_stage_pipelines_compile_for_v5e(one_chip, pipeline, kernels):
    spec, p, x, w, _ = _layer("VN3.2", one_chip)
    hlo = ops.conv2d_pallas.lower(
        x, w, m=p.m, pad=spec.pad, pipeline=pipeline,
        interpret=False).compile().as_text()
    calls = tpu_kernel_calls(hlo)
    assert all(calls[k] == 1 for k in kernels), calls


@pytest.mark.parametrize("name", ["VN1.2", "VN4.2"])
def test_fused_bwd_compiles_for_v5e(one_chip, name):
    spec, p, x, w, gy = _layer(name, one_chip)
    assert ops.fused_bwd_eligible(x.shape, w.shape, p.m, spec.pad)
    hlo = ops.conv2d_fused_bwd.lower(
        x, w, gy, m=p.m, pad=spec.pad, interpret=False).compile().as_text()
    assert tpu_kernel_calls(hlo)["wino_fused_bwd"] == 1, hlo[:2000]
