"""Every convolution names its operations: one scope per conv, one per stage.

Small instances are lowered and compiled on the host, and each compiled
instruction's ``op_name`` (its name-scope path) is read from the HLO text.
A profiler trace on the chip carries the same path for each device
operation, which is how the benchmark charges device time to a conv.

Instructions that XLA makes itself carry no operation's path: copies
that copy insertion adds have no metadata, and a layout change of an
argument takes the argument's name, or for an argument of an inner
``jit`` that call's path, which ends in ``jit(...)``.  Every instruction,
parameters and constants aside, that is not one of the first two kinds
carries exactly one conv key, the key parses back to the call's shape,
and every operation has a stage of ``STAGES`` where its route has stages.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import conv2d
from repro.core.conv import conv_scope
from repro.launch.mesh import make_host_mesh
from repro.models import cnn

KEY = re.compile(r"conv_r(\d+)s(\d+)_c(\d+)k(\d+)_h(\d+)w(\d+)")
STAGES = {"tiles", "pad", "filter", "kernel", "crop", "epilogue"}
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%\S+ = ")
OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def instructions(hlo: str):
    """(opcode, op_name or None) of every instruction of the HLO text."""
    for line in hlo.splitlines():
        if INSTRUCTION.match(line):
            rhs = line[line.index(" = "):]
            name = OP_NAME.search(rhs)
            yield OPCODE.search(rhs).group(1), name and name.group(1)


def scope(op_name: str) -> tuple[set, str, str]:
    """(conv keys, route, stage) of a path: the route is the component
    below the key, the stage the next one that is not a ``jit(...)``
    wrapper, short of the primitive's own name at the end."""
    paths = [p.split("/") for p in op_name.split(";")]
    keys = {c for p in paths for c in p if KEY.fullmatch(c)}
    if len(keys) != 1:
        return keys, "", ""
    path = next(p for p in paths if keys <= set(p))
    below = [c for c in path[path.index(next(iter(keys))) + 1:-1]
             if not c.startswith("jit(")]
    if below and below[0] == "epilogue":
        return keys, "", "epilogue"
    return keys, *(below + ["", ""])[:2]


def key_shape(key: str) -> tuple[int, ...]:
    return tuple(int(g) for g in KEY.fullmatch(key).groups())


def check_scopes(fn, args, shape, route, stages):
    """Compile ``fn(*args)``; every instruction of the program carries the
    one key of ``shape`` (r, stride, C, K, H, W), the route ``route``, and a
    stage from ``stages`` (every instruction of the route itself has one
    when ``stages`` holds more than the epilogue)."""
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    ops = list(instructions(hlo))
    arguments = {name for op, name in ops if op == "parameter"}
    seen_stages, keyed = set(), 0
    for op, name in ops:
        if op in ("parameter", "constant"):
            continue
        if name is None or name in arguments:   # made by XLA itself
            continue
        keys, found_route, stage = scope(name)
        assert len(keys) == 1, (op, name)
        assert key_shape(next(iter(keys))) == shape, name
        if name.rsplit("/", 1)[-1].startswith("jit("):  # an inner call's argument
            continue
        keyed += 1
        if stage == "epilogue":
            seen_stages.add(stage)
            continue
        assert found_route == route, name
        if stages - {"epilogue"}:
            assert stage in STAGES, name
            seen_stages.add(stage)
        else:
            assert stage not in STAGES, name
    assert keyed, "no instruction of the program found"
    assert seen_stages == stages


def _xw(N, H, W, C, K, r=3, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, (N, H, W, C), jnp.float32),
            jax.random.normal(kw, (r, r, C, K), jnp.float32))


# Six channels, padded to eight, so the filter has a ``pad`` stage: the
# input tiles come out of ``tiles`` already padded.
PIPELINE_STAGES = {"tiles", "pad", "filter", "kernel", "crop"}
SHARDED_STAGES = {"tiles", "filter", "kernel", "crop"}

# (algorithm, stride, stages of the route)
ROUTES = [
    ("direct", 1, set()),
    ("direct", 2, set()),
    ("winograd", 1, set()),
    ("winograd_fused_e2e", 1, PIPELINE_STAGES),
]


@pytest.mark.parametrize("algorithm,stride,stages", ROUTES,
                         ids=[f"{a}-s{s}" for a, s, _ in ROUTES])
def test_conv2d_names_its_conv_route_and_stages(algorithm, stride, stages):
    x, w = _xw(1, 10, 12, 6, 16)
    check_scopes(
        lambda x, w: conv2d(x, w, stride=stride, pad=1, algorithm=algorithm),
        (x, w), (3, stride, 6, 16, 10, 12), algorithm, stages)


@pytest.mark.parametrize("differentiable", [True, False],
                         ids=["custom_vjp", "plain"])
def test_mesh_forward_names_its_conv_route_and_stages(differentiable):
    mesh = make_host_mesh()
    x, w = _xw(2, 10, 10, 8, 8)
    check_scopes(
        lambda x, w: conv2d(x, w, pad=1, algorithm="winograd", m=2,
                            mesh=mesh, parallel_mode="data",
                            differentiable=differentiable),
        (x, w), (3, 1, 8, 8, 10, 10), "winograd_sharded", SHARDED_STAGES)


def test_conv_block_charges_its_epilogue_to_the_conv():
    p = {**cnn._conv_init(jax.random.PRNGKey(0), 3, 6, 16),
         "affine": {"scale": jnp.full((16,), 1.5), "shift": jnp.ones((16,))}}
    x, _ = _xw(1, 10, 10, 6, 16)
    check_scopes(
        lambda p, x: cnn.conv_block(p, x, pad=1,
                                    algorithm="winograd_fused_e2e"),
        (p, x), (3, 1, 6, 16, 10, 10), "winograd_fused_e2e",
        PIPELINE_STAGES | {"epilogue"})


@pytest.mark.parametrize("x_shape,w_shape,stride", [
    ((32, 224, 224, 3), (3, 3, 3, 64), 1),
    ((32, 224, 224, 3), (7, 7, 3, 64), 2),
    ((1, 56, 56, 64), (1, 1, 64, 256), 1),
])
def test_conv_scope_parses_back_to_the_shape(x_shape, w_shape, stride):
    key = conv_scope(x_shape, w_shape, stride)
    N, H, W, C = x_shape
    r, _, _, K = w_shape
    assert "/" not in key
    assert key_shape(key) == (r, stride, C, K, H, W)
