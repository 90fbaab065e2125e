#!/usr/bin/env python3
"""Serve VGG-16 at full width on a TPU through the compiled Winograd kernels.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the (2, 2) mesh path on four chips

One chip: VGG-16 at width 1.0 on 224x224x3 images with 1000 classes and
random weights made from ``--seed``.  Every 3x3 conv is planned by
``conv2d(algorithm="auto")``.  The model is served through
``ConvServeEngine.infer`` (three batches of 8 images) and through
``CoalescingConvServeEngine`` (one batch coalesced from ragged requests).
The smoke checks that:

  * the Pallas kernels are compiled, not interpreted
    (``kernels.common.default_interpret()`` is False), and the compiled
    forward holds one ``tpu_custom_call`` of the planned forward kernel for
    every conv that the plan routes to Pallas, counted by kernel name;
  * the whole logits tensor of every batch matches the same forward with
    ``algorithm="direct"`` (XLA's convolution) run at full f32 precision on
    the same chip, within the bound written below.

Four chips (``--chips 4``) run only the mesh path and what it is compared
with: the same engine on a (2, 2) ("data", "model") mesh, once with the
plan's per-layer parallel mode and once with each of "data", "2d" and
"model" forced, each against the one-chip result computed on device 0 in
the same process.  The compiled HLO of each mode must hold collectives, and
the batch must be laid out over all four devices.

Lines before the last say what was found; times are wall time of this
smoke, not metrics.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The script
exits non-zero, without that line, when JAX finds no TPU, when a kernel
would run interpreted, when a check fails, or when any phase raises.  It
runs in one process and starts no other.
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.plan import plan_for_conv  # noqa: E402
from repro.core.winograd import PRECISION  # noqa: E402
from repro.kernels import common  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models.cnn import vgg16_forward, vgg16_init  # noqa: E402
from repro.serve import CoalescingConvServeEngine, ConvServeEngine  # noqa: E402

BATCH, SIZE, CLASSES = 8, 224, 1000
N_BATCHES = 3
RAGGED_REQUESTS = (3, 1, 4)          # image counts, coalesced into one batch

#: Forward kernel of each Pallas pipeline, as named in the compiled HLO.
FORWARD_KERNEL = {"fused_e2e": "wino_fused_e2e", "fused": "wino_fused",
                  "nonfused": "wino_gemm"}

# Bound on |logits - reference|.  One f32 conv layer on the Winograd path
# matches XLA's convolution within atol 5e-4, rtol 2e-3
# (tests/test_conv_golden.py).  VGG-16 stacks 13 such layers between ReLUs
# and max-pools, which are 1-Lipschitz, and He-initialised weights keep the
# activation scale near 1 from layer to layer, so per-layer errors add at
# most linearly: the logits must agree within 13 times that tolerance.
DEPTH = 13
ATOL, RTOL = DEPTH * 5e-4, DEPTH * 2e-3

#: Collective ops in HLO text.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
#: Collectives that each parallel mode must compile to, any one of them:
#: "model" sums partial products over "data" (all-reduce), "2d" gathers
#: its K-sharded output blocks, and "data" splits only the tile axis, so
#: each chip convolves its own images and needs none.
MODE_COLLECTIVES = {"planned": COLLECTIVES, "data": (), "2d": COLLECTIVES,
                    "model": ("all-reduce",)}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def compare(got: jax.Array, ref: jax.Array, what: str) -> None:
    """Whole-tensor check of logits against a reference."""
    got, ref = np.asarray(got), np.asarray(ref)
    check(got.shape == ref.shape, f"{what}: shape {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), f"{what}: non-finite logits")
    err = np.abs(got - ref)
    worst = float(err.max())
    ok = bool((err <= ATOL + RTOL * np.abs(ref)).all())
    log(f"{what}: max |err| {worst:.3e} (max |ref| "
        f"{float(np.abs(ref).max()):.3e}; bound {ATOL:.1e} + {RTOL:.1e}|ref|)"
        f" -> {'ok' if ok else 'FAIL'}")
    check(ok, f"{what}: logits outside the bound")


def vgg16_conv_plans(params, batch: int) -> list:
    """The ConvPlan that conv2d(algorithm="auto") resolves for each conv."""
    plans, size = [], SIZE
    for convs in params["stages"]:
        for p in convs:
            C, K = p["w"].shape[2], p["w"].shape[3]
            plans.append(((size, C, K), plan_for_conv(
                (batch, size, size, C), p["w"].shape, pad=1, elt_bytes=4)))
        size //= 2
    return plans


def images(seed: int, n: int) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(seed), (n, SIZE, SIZE, 3),
                             jnp.float32)


_direct_forward = jax.jit(functools.partial(vgg16_forward, algorithm="direct"))


def direct_reference(params, x: jax.Array) -> jax.Array:
    """The same forward on XLA's convolution, at full f32 precision."""
    with jax.default_matmul_precision("highest"):
        return _direct_forward(params, x)


def one_chip(params, seed: int) -> None:
    check(not common.default_interpret(),
          "Pallas kernels would run in interpret mode")
    log("kernels compiled (interpret=False); in-kernel f32 dot precision "
        f"{PRECISION.name}")

    plans = vgg16_conv_plans(params, BATCH)
    expected = collections.Counter()
    for i, ((size, C, K), p) in enumerate(plans):
        blocks = p.kernel_kwargs()
        log(f"conv {i + 1:2d} {size}x{size} {C:3d}->{K:3d}: {p.algorithm} "
            f"m={p.m} blocks={tuple(blocks.values()) or '-'}")
        if p.pipeline is not None:
            expected[FORWARD_KERNEL[p.pipeline]] += 1
    log(f"plan routes {sum(expected.values())} of {len(plans)} convs to "
        f"Pallas: {dict(expected)}")

    engine = ConvServeEngine(vgg16_forward, params)
    batches = [images(seed + 1 + b, BATCH) for b in range(N_BATCHES)]
    t0 = time.perf_counter()
    hlo = engine.lower(batches[0]).compile().as_text()
    log(f"compiled the served forward in {time.perf_counter() - t0:.1f} s "
        "(wall time of the smoke)")
    found = common.tpu_kernel_calls(hlo)
    log(f"tpu_custom_call kernels in the compiled forward: {dict(found)}")
    for kernel in FORWARD_KERNEL.values():
        check(found[kernel] == expected[kernel],
              f"{found[kernel]} {kernel} kernels in the HLO, the plan routes "
              f"{expected[kernel]} convs to it")

    for b, x in enumerate(batches):
        got = jax.block_until_ready(engine.infer(x))
        compare(got, direct_reference(params, x), f"batch {b + 1} of {BATCH}")

    coalescer = CoalescingConvServeEngine(vgg16_forward, params)
    ragged = images(seed + 1 + N_BATCHES, sum(RAGGED_REQUESTS))
    tickets, ofs = [], 0
    for n in RAGGED_REQUESTS:
        tickets.append(coalescer.submit(ragged[ofs:ofs + n]))
        ofs += n
    t0 = time.perf_counter()
    results = coalescer.flush()
    jax.block_until_ready(results)
    log(f"coalesced requests of {RAGGED_REQUESTS} images into "
        f"{coalescer.coalesced_dispatches} dispatch in "
        f"{time.perf_counter() - t0:.1f} s (wall time, compile included)")
    check(coalescer.coalesced_dispatches == 1, "requests were not coalesced")
    got = jnp.concatenate([results[t] for t in tickets], axis=0)
    compare(got, direct_reference(params, ragged), "coalesced ragged batch")


def four_chips(params, seed: int) -> None:
    devices = jax.devices()
    mesh = make_host_mesh(dp=2, tp=2)
    check(dict(mesh.shape) == {"data": 2, "model": 2},
          f"mesh shape {dict(mesh.shape)}")
    check(set(mesh.devices.flat) == set(devices),
          "the mesh does not span all four chips")
    x = images(seed + 1, BATCH)

    # the reference: the one-chip Pallas path, on device 0
    on0 = jax.device_put((params, x), devices[0])
    ref = jax.block_until_ready(ConvServeEngine(vgg16_forward, on0[0]).infer(
        on0[1]))
    log(f"one-chip reference on {devices[0]}: logits {ref.shape}")

    # Compile the four mesh programs at once: XLA compiles off the GIL.
    engines = {mode or "planned": ConvServeEngine(
        vgg16_forward, params, mesh=mesh, parallel_mode=mode)
        for mode in (None, "data", "2d", "model")}
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(len(engines)) as pool:
        futures = {name: pool.submit(lambda e: e.lower(x).compile(), engine)
                   for name, engine in engines.items()}
        compiled = {name: f.result() for name, f in futures.items()}
    log(f"compiled the {len(engines)} mesh programs in "
        f"{time.perf_counter() - t0:.1f} s (wall time)")

    for name, engine in engines.items():
        hlo = compiled[name].as_text()
        ops = {c: hlo.count(f" {c}(") + hlo.count(f" {c}-start(")
               for c in COLLECTIVES}
        ops = {c: n for c, n in ops.items() if n}
        batch_sharding = compiled[name].input_shardings[0][1]
        log(f"mode {name}: collectives {ops}; batch sharding "
            f"{batch_sharding.spec} over {len(batch_sharding.device_set)} "
            "chips")
        need = MODE_COLLECTIVES[name]
        check(not need or any(c in ops for c in need),
              f"mode {name}: none of {need} in the compiled HLO")
        check(len(batch_sharding.device_set) == 4
              and batch_sharding.shard_shape(x.shape)[0] < BATCH,
              f"mode {name}: the batch is not split over the four chips")
        got = jax.block_until_ready(engine.infer(x))
        check(len(got.sharding.device_set) == 4,
              f"mode {name}: the logits are not on all four chips")
        compare(got, ref, f"mode {name} vs one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache_dir = enable_compile_cache()

    devices = jax.devices()
    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}")
    if dev.platform != "tpu":
        log(f"no TPU: JAX found {dev.platform} devices")
        return 2
    if len(devices) < args.chips:
        log(f"{args.chips} chips asked for, {len(devices)} found")
        return 2
    log(f"compile cache: {cache_dir}")

    params = vgg16_init(jax.random.PRNGKey(args.seed), width_mult=1.0,
                        n_classes=CLASSES)
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(params, args.seed)
    log(f"all checks passed in {time.perf_counter() - t0:.1f} s (wall time)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
