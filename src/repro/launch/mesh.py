"""Production mesh construction (assignment-fixed shapes).

FUNCTIONS, not module-level constants: importing this module never touches
jax device state.

  single pod : (16, 16)      axes ("data", "model")        = 256 chips
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

The "pod" axis is pure data parallelism (gradient all-reduce only crosses
it); scaling to 1000+ nodes extends this axis -- nothing else in the
sharding rules references its extent.

``host_mesh`` builds the simulated multi-device CPU mesh used by the
parallel-execution tests and the measured fig9 column: XLA splits one host
CPU into n independent devices via
``--xla_force_host_platform_device_count``, which exercises the real SPMD
partitioner and real (shared-memory) collectives.  The flag only takes
effect before the backend initializes, so callers that need it set the
environment up front (tests/conftest.py honours REPRO_HOST_DEVICES; the
benchmark driver sets XLA_FLAGS at module top, like launch/dryrun.py).
"""

from __future__ import annotations

import os

import jax

HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(*, dp: int | None = None, tp: int = 1):
    """Mesh over whatever devices exist (tests / real runs on this host)."""
    n = jax.device_count()
    dp = dp or (n // tp)
    assert dp * tp <= n, (dp, tp, n)
    return jax.make_mesh((dp, tp), ("data", "model"), axis_types=_auto(2))


def request_host_devices(n: int) -> None:
    """Ask XLA for n simulated host devices.  Must run before jax touches
    the backend (first device/array use locks the count).  An existing
    device-count flag in XLA_FLAGS wins -- the caller set it deliberately
    (``host_mesh`` still checks the count that actually materialized)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if HOST_DEVICE_FLAG in flags:
        return
    os.environ["XLA_FLAGS"] = f"{flags} {HOST_DEVICE_FLAG}={n}".strip()


def host_mesh(n: int = 8, *, tp: int = 2):
    """(n/tp, tp) ("data", "model") mesh over n simulated host devices.

    Requires the process to actually have n devices -- i.e. it was started
    with ``XLA_FLAGS={HOST_DEVICE_FLAG}=n`` (or ``request_host_devices``
    ran before backend init).  Raises with that instruction otherwise, so
    test fixtures can translate the failure into a re-exec or skip.
    """
    have = jax.device_count()
    if have < n:
        raise RuntimeError(
            f"host_mesh({n}) needs {n} devices, found {have}; start the "
            f"process with XLA_FLAGS={HOST_DEVICE_FLAG}={n} (see "
            f"tests/conftest.py REPRO_HOST_DEVICES)")
    assert n % tp == 0, (n, tp)
    return jax.make_mesh((n // tp, tp), ("data", "model"),
                         axis_types=_auto(2))
