"""Sharding rules, divisibility degrade, loop-aware HLO cost extraction,
plus a multi-device numeric-equivalence subprocess test (mesh == 1 device).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import hlo_costs as HC
from repro.parallel.sharding import PARAM_RULES, _spec_for_path, param_pspecs

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_rules_hit_expected_paths():
    cases = {
        "blocks/attn/wq": ("fsdp", "model", None),
        "blocks/mlp/w_gate": ("fsdp", "model"),
        "blocks/moe/experts/w_down": ("model", None, "fsdp"),
        "embed/table_tied": ("model", None),
        "embed/unembed": ("fsdp", "model"),
    }
    for path, want in cases.items():
        got = _spec_for_path(path, len(want), (1024,) * len(want))
        assert tuple(got) == want, (path, got)


def test_param_rules_stacked_leading_axis():
    got = _spec_for_path("blocks/attn/wq", 4, (8, 512, 16, 64))
    assert tuple(got) == (None, "fsdp", "model", None)


def test_param_pspecs_tree():
    from repro import configs
    from repro.models.api import build

    cfg = configs.get_smoke_config("phi35_moe_42b")
    api = build(cfg)
    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    specs = param_pspecs(params)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    hits = {"/".join(str(getattr(k, "key", k)) for k in p): s for p, s in flat}
    assert any("experts" in k and "model" in tuple(v)
               for k, v in hits.items() if hasattr(v, "__iter__"))


# --------------------------- hlo cost extraction ---------------------------

def test_loop_aware_flops_exact():
    """7-iteration scanned matmul: loop-aware count == hand count; builtin
    cost_analysis undercounts by the trip count."""
    def f(a, b):
        def body(c, _):
            return c @ b, None
        c, _ = jax.lax.scan(body, a, None, length=7)
        return c

    M = 64
    a = jax.ShapeDtypeStruct((M, M), jnp.float32)
    compiled = jax.jit(f).lower(a, a).compile()
    hc = HC.analyze_hlo(compiled.as_text())
    assert hc.flops == pytest.approx(7 * 2 * M**3, rel=1e-6)
    assert hc.while_loops >= 1


def test_nested_loop_multiplicity():
    def f(a, b):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ b, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        c, _ = jax.lax.scan(outer, a, None, length=5)
        return c

    M = 32
    a = jax.ShapeDtypeStruct((M, M), jnp.float32)
    compiled = jax.jit(f).lower(a, a).compile()
    hc = HC.analyze_hlo(compiled.as_text())
    assert hc.flops == pytest.approx(15 * 2 * M**3, rel=1e-6)


def test_collective_parse_synthetic():
    hlo = textwrap.dedent("""\
    HloModule m

    ENTRY %main (p: f32[8,16]) -> f32[8,16] {
      %p = f32[8,16]{1,0} parameter(0)
      %ar = f32[8,16]{1,0} all-reduce(%p), replica_groups={}
      ROOT %ag = f32[8,16]{1,0} all-gather(%ar), dimensions={0}
    }
    """)
    hc = HC.analyze_hlo(hlo)
    n = 8 * 16 * 4
    assert hc.collective_by_kind["all-reduce"] == 2.0 * n
    assert hc.collective_by_kind["all-gather"] == 1.0 * n


def test_tuple_type_with_index_comments_parses():
    line = ("  %while.376 = (s32[], f32[256,1,2,512]{3,2,1,0}, "
            "/*index=5*/s32[4,1,1024]{2,1,0}) while(%tuple.1), "
            "condition=%cond, body=%body")
    parsed = HC._parse_def(line)
    assert parsed is not None and parsed[2] == "while"


# ------------------------ multi-device equivalence ------------------------

_EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.models.api import build
from repro.optim import adamw
from repro.train import build_train_step, init_state
from repro.parallel import specs as S
from repro.launch.mesh import make_host_mesh
from repro.data import SyntheticTokens

cfg = configs.get_smoke_config("chatglm3_6b")
api = build(cfg)
opt = adamw(1e-2)
pipe = SyntheticTokens(vocab=cfg.vocab, seq=32, global_batch=8, seed=0)
batch = pipe.batch_at(0)
step = build_train_step(api, opt, microbatches=2)

# single device
s0 = init_state(api, opt, jax.random.PRNGKey(0))
s0, m0 = jax.jit(step)(s0, batch)

# 4x2 mesh with full sharding machinery
mesh = make_host_mesh(dp=4, tp=2)
with jax.set_mesh(mesh):
    s1 = init_state(api, opt, jax.random.PRNGKey(0))
    sh = S.state_shardings(jax.eval_shape(lambda: s1), mesh)
    b_sh = S.batch_shardings(batch, mesh)
    f = jax.jit(step, in_shardings=(sh, b_sh), out_shardings=(sh, None))
    s1, m1 = f(jax.device_put(s1, sh), jax.device_put(batch, b_sh))

np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]), rtol=1e-4)
w0 = jax.tree_util.tree_leaves(s0.params)[2]
w1 = jax.tree_util.tree_leaves(s1.params)[2]
np.testing.assert_allclose(np.asarray(w0), np.asarray(w1), atol=2e-4, rtol=2e-3)

# serve path: decode on mesh == decode off mesh
cache = api.init_cache(8, 40)
lg, _ = api.prefill(s0.params, batch, cache)
with jax.set_mesh(mesh):
    cache2 = api.init_cache(8, 40)
    lg2, _ = jax.jit(lambda p, b, c: api.prefill(p, b, c))(s1.params, batch, cache2)
np.testing.assert_allclose(np.asarray(lg), np.asarray(lg2), atol=3e-3)
print("EQUIV-OK")
"""


@pytest.mark.slow
def test_mesh_numeric_equivalence_subprocess():
    """Full train step + prefill on a 4x2 host mesh == single device."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", _EQUIV_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "EQUIV-OK" in out.stdout, out.stdout + "\n" + out.stderr
