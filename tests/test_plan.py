"""The ConvPlan layer: caching, hashability, decision quality, the e2e
pipeline's numerical contract, and the plan-driven serving engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv2d
from repro.core.plan import (
    ConvSpec,
    LMWorkloadSpec,
    clear_plan_cache,
    plan,
    plan_cache_info,
    plan_for_conv,
    plan_lm,
)
from repro.core.winograd import winograd_conv2d_reference
from repro.models.cnn import TABLE1_LAYERS, layer_plans
from repro.parallel.strategy import MODES


def _spec(**kw):
    base = dict(N=1, H=56, W=56, C=64, K=64, r=3, pad=1)
    base.update(kw)
    return ConvSpec(**base)


# ------------------------------ plan basics ------------------------------

def test_plan_cache_hits_on_repeated_shapes():
    clear_plan_cache()
    p1 = plan(_spec())
    misses = plan_cache_info().misses
    p2 = plan(_spec())
    p3 = plan(ConvSpec(N=1, H=56, W=56, C=64, K=64, r=3, pad=1))
    assert plan_cache_info().misses == misses     # no re-planning
    assert plan_cache_info().hits >= 2
    assert p1 is p2 is p3                         # lru returns the cached object


def test_plan_equality_and_hashability():
    p1, p2 = plan(_spec()), plan(_spec())
    assert p1 == p2 and hash(p1) == hash(p2)
    other = plan(_spec(C=128))
    assert p1 != other
    table = {p1: "a", other: "b"}                 # usable as a dict/jit key
    assert table[p2] == "a"


def test_plan_decides_everything():
    p = plan(_spec(C=256, K=256))
    assert p.algorithm in ("winograd_fused_e2e", "winograd_fused")
    assert p.m in (2, 4, 6)
    assert p.blocks is not None
    assert p.parallel_mode in MODES
    assert p.t_est > 0 and p.hbm_bytes > 0 and p.flops > 0


def test_plan_ineligible_goes_direct():
    assert plan(_spec(stride=2)).algorithm == "direct"
    assert plan(ConvSpec(N=1, H=14, W=14, C=8, K=8, r=1)).algorithm == "direct"
    p = plan(_spec(stride=2))
    assert p.m is None and p.blocks is None


def test_plan_prefers_single_pass_when_vmem_fits():
    for spec in (_spec(), _spec(C=512, K=512, H=28, W=28)):
        assert plan(spec).algorithm == "winograd_fused_e2e"


def test_plan_for_conv_matches_auto_dispatch():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 20, 20, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 8), jnp.float32)
    p = plan_for_conv(x.shape, w.shape, pad=1)
    explicit = conv2d(x, w, pad=1, algorithm=p.algorithm, m=p.m,
                      differentiable=False)
    auto = conv2d(x, w, pad=1, algorithm="auto", differentiable=False)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(explicit),
                               atol=1e-5, rtol=1e-5)


# -------------------- e2e pipeline: numbers and model --------------------

@pytest.mark.parametrize("m", [2, 4, 6])
@pytest.mark.parametrize("shape", [(1, 18, 20, 8, 16), (2, 13, 11, 5, 7)])
def test_fused_e2e_matches_reference_ragged(m, shape):
    """winograd_fused_e2e == pure-JAX reference across F(m,3), including
    ragged tile edges (the acceptance contract)."""
    N, H, W, C, K = shape
    kx, kw = jax.random.split(jax.random.PRNGKey(m))
    x = jax.random.normal(kx, (N, H, W, C), jnp.float32)
    w = jax.random.uniform(kw, (3, 3, C, K), jnp.float32, -1.0, 1.0)
    ref = winograd_conv2d_reference(x, w, m, pad=1)
    got = conv2d(x, w, pad=1, algorithm="winograd_fused_e2e", m=m,
                 differentiable=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=3e-4, rtol=1e-3)


def test_fused_e2e_gradients():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 12, 4), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 4, 8), jnp.float32)

    def loss_e2e(x, w):
        y = conv2d(x, w, pad=1, algorithm="winograd_fused_e2e", m=2)
        return jnp.sum(jnp.square(y))

    def loss_ref(x, w):
        return jnp.sum(jnp.square(winograd_conv2d_reference(x, w, 2, pad=1)))

    gx_p, gw_p = jax.grad(loss_e2e, argnums=(0, 1))(x, w)
    gx_d, gw_d = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_d),
                               atol=5e-3, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(gw_p), np.asarray(gw_d),
                               atol=5e-3, rtol=5e-3)


def test_e2e_modeled_bytes_below_fused_for_table1_layers():
    """The single-pass pipeline's modeled HBM bytes are strictly below the
    two-stage fused pipeline's for every Table-1 layer (at each layer's
    planned blocking)."""
    from repro.core import blocking

    for spec in TABLE1_LAYERS:
        for m in (2, 4, 6):
            P = spec.H + 2 * spec.pad - spec.r + 1
            T = (-(-P // m)) ** 2
            e2e = blocking.choose_blocks(T, spec.C, spec.K, m, spec.r,
                                         pipeline="fused_e2e")
            fused = blocking.choose_blocks(T, spec.C, spec.K, m, spec.r,
                                           pipeline="fused")
            assert e2e is not None, (spec.name, m)
            assert e2e.hbm_bytes_e2e < fused.hbm_bytes_fused_pipeline, \
                (spec.name, m)


def test_layer_plans_table1():
    plans = layer_plans(TABLE1_LAYERS)
    assert len(plans) == len(TABLE1_LAYERS)
    for spec, p in plans:
        assert p.algorithm.startswith("winograd_"), spec.name
        assert p.parallel_mode in MODES
    # repeated resolution is pure cache hits
    before = plan_cache_info().hits
    layer_plans(TABLE1_LAYERS)
    assert plan_cache_info().hits >= before + len(TABLE1_LAYERS)


# --------------------------- LM workload plans ---------------------------

def test_plan_lm_modes_and_microbatches():
    small_dense = LMWorkloadSpec(6e9, False, "train", 256)
    assert plan_lm(small_dense).parallel_mode == "dp"
    assert plan_lm(small_dense).microbatches == 8
    big = LMWorkloadSpec(123e9, False, "train", 256)
    assert plan_lm(big).parallel_mode == "2d"
    assert plan_lm(big).microbatches == 16
    moe = LMWorkloadSpec(42e9, True, "train", 256)
    assert plan_lm(moe).parallel_mode == "2d"
    decode = LMWorkloadSpec(6e9, False, "decode", 128)
    assert plan_lm(decode).parallel_mode == "2d"
    assert plan_lm(decode).microbatches == 1
    assert plan_lm(LMWorkloadSpec(6e9, False, "train", 8)).microbatches == 1


# ------------------------- plan-driven serving -------------------------

def test_conv_serve_engine_amortizes_plans():
    from repro.models import cnn
    from repro.serve import ConvServeEngine

    def forward(params, x, *, algorithm="auto"):
        x = cnn.conv_block(params["c1"], x, pad=1, algorithm=algorithm)
        return cnn.conv_block(params["c2"], x, pad=1, algorithm=algorithm)

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {"c1": cnn._conv_init(k1, 3, 4, 8), "c2": cnn._conv_init(k2, 3, 8, 8)}
    engine = ConvServeEngine(forward, params)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 20, 20, 4), jnp.float32)

    y1 = engine.infer(x)
    hits_after_first = plan_cache_info().hits
    y2 = engine.infer(x)                       # same signature: jit cache
    assert engine.compiled_signatures == 1
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))

    x2 = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 20, 4), jnp.float32)
    engine.infer(x2)                           # new signature, same layers
    assert engine.compiled_signatures == 2
    assert plan_cache_info().hits >= hits_after_first

    ref = forward(params, x, algorithm="winograd")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(ref),
                               atol=5e-4, rtol=5e-3)
