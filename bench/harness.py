"""Run one benchmark cell once and print the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a configuration
under a traffic mix.  Everything that belongs to one of them is found by
name, so a new cell needs new files and entries only:

* ``configs/<config>.json``: the configuration as it is run (sizes, dtype,
  matmul precision), with its plain reference and weight generator beside
  it in ``configs/<config>.py``;
* ``models/<config>.py``: the served network, the configuration's layers
  composed from the program's convolution block (``forward``);
* ``traffic/<traffic>.json``: the mix's parameters; its ``kind`` names the
  loop in ``loops/<kind>.py`` that generates and drives it;
* ``limits/<workload>.json``: the limit of each number that the
  correctness check compares, with the readings it was set from;
* ``metrics/<metric>.py``: one reader per per-layer metric, with
  ``read(view) -> float | None``, read in the cells that its entry's
  ``workloads`` lists.

A loop's ``run(cell)`` makes the weights and inputs from the seed, warms
up every shape it will use, measures for ``cell.seconds`` inside
``cell.window()``, and then checks what the timed path produced against
the configuration's reference.  It returns a ``Result``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

# The compile cache lives at a fixed path inside the checkout, whatever the
# environment says, so that two checkouts never share it.  It must be set
# before JAX reads its configuration.
CACHE_DIR = ROOT / ".jax_cache"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import refs  # noqa: E402
import xtrace  # noqa: E402
import work  # noqa: E402


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def require_accelerator(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator, only the CPU")
    if len(devices) < chips:
        raise NoAccelerator(f"{chips} chips asked for, {len(devices)} found")
    return devices[:chips]


@dataclasses.dataclass
class Result:
    """What a loop measured and what its check read."""

    attempted: int
    failed: int
    e2e: dict[str, float]              # end-to-end metrics by name
    record: dict[str, Any]             # host record for per-layer readers
    checks: dict[str, float]           # compared numbers by name
    control: dict[str, dict] = dataclasses.field(default_factory=dict)


class Window:
    """The measured window: ``over()`` says when to stop dispatching."""

    def __init__(self, seconds: float):
        self.seconds_asked = seconds
        self.t0 = time.perf_counter()
        self.t1: float | None = None

    def over(self) -> bool:
        return time.perf_counter() - self.t0 >= self.seconds_asked

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def span(name: str):
    """A host span in the profiler's trace (``bench.<name>``)."""
    return jax.profiler.TraceAnnotation(f"bench.{name}")


@dataclasses.dataclass
class Cell:
    """One cell, one seed: what a loop needs to run it."""

    workload: dict
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    control: bool = False              # calibration: read the control too
    cache: dict = dataclasses.field(default_factory=dict)
    setup_s: float | None = None
    memory_peak_bytes: int | None = None
    reduced_trace: dict | None = None
    window_compiles: int = 0

    @functools.cached_property
    def ref(self):
        return load_module(BENCH / "configs" / f"{self.cfg['name']}.py",
                           f"ref_{self.cfg['name']}")

    @functools.cached_property
    def forward(self):
        """The served network: ``forward(params, x, *, algorithm)``."""
        model = load_module(BENCH / "models" / f"{self.cfg['name']}.py",
                            f"model_{self.cfg['name']}")
        return model.forward

    def key(self, tag: int) -> jax.Array:
        return jax.random.fold_in(refs.seed_key(self.seed), tag)

    def weights(self):
        """The weights, made on the device in one call from the seed."""
        init = self.cache.get("init")
        if init is None:
            init = self.cache["init"] = jax.jit(
                functools.partial(self.ref.init, cfg=self.cfg))
        return init(self.key(0))

    def images(self, shape: tuple[int, ...]) -> jax.Array:
        S, C = self.cfg["image_size"], self.cfg["in_channels"]
        return jax.jit(lambda k: jax.random.normal(
            k, (*shape, S, S, C), jnp.float32))(self.key(1))

    def reference(self, precision: str):
        """The configuration's plain forward, jitted, at ``precision``."""
        fn = self.cache.get(("ref", precision))
        if fn is None:
            fn = self.cache[("ref", precision)] = jax.jit(functools.partial(
                self.ref.forward, conv=refs.conv(precision),
                matmul=refs.matmul(precision)))
        return fn

    @contextlib.contextmanager
    def window(self):
        """Set-up ends here.  Traces the window when asked, counts the
        compilations inside it, and reads the peak memory after it."""
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if self.trace else None
        compiles = []
        listener = (lambda event, *_a, **_k: compiles.append(event)
                    if "backend_compile" in event else None)
        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            if trace_dir:
                jax.profiler.start_trace(trace_dir)
            # Objects made in set-up move out of the collector's way, so
            # that its passes in the window stay short.
            gc.collect()
            gc.freeze()
            self.setup_s = time.perf_counter() - self.t_start
            win = Window(self.seconds)
            yield win
            win.t1 = time.perf_counter()
            if trace_dir:
                jax.profiler.stop_trace()
                t = time.perf_counter()
                self.reduced_trace = xtrace.reduce_trace(
                    xtrace.find_xplane(trace_dir))
                self.reduced_trace["window_s"] = win.seconds
                self.reduced_trace["reduce_s"] = time.perf_counter() - t
        finally:
            gc.unfreeze()
            jax.monitoring.unregister_event_duration_listener(listener)
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        self.window_compiles = len(compiles)
        # On a TPU the programs' temporaries are reserved apart from the
        # buffers in use, so the peak in use alone leaves them out.  The
        # sum of the two peaks is an upper bound on the chip's peak, and
        # equals it where the reservation was at its peak when the buffers
        # in use were.
        stats = [d.memory_stats() or {} for d in self.devices]
        self.memory_peak_bytes = max(
            (s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
             for s in stats), default=0)


def cell_for(workload_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, devices: list, bench: dict | None = None,
             **kw) -> Cell:
    bench = bench or benchmark()
    workloads = {w["name"]: w for w in bench["workloads"]}
    if workload_name not in workloads:
        raise KeyError(f"no workload {workload_name!r} in BENCHMARK.json: "
                       f"{sorted(workloads)}")
    wl = workloads[workload_name]
    cfg = load_json(BENCH / "configs" / f"{wl['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{wl['traffic']}.json")
    return Cell(workload=wl, cfg=cfg, traffic=traffic, seed=seed,
                seconds=seconds, trace=trace, t_start=t_start,
                devices=devices, **kw)


def limits(workload_name: str) -> dict[str, float]:
    table = load_json(BENCH / "limits" / f"{workload_name}.json")
    return {k: v["limit"] for k, v in table["limits"].items()}


def run_loop(cell: Cell) -> Result:
    loop = load_module(BENCH / "loops" / f"{cell.traffic['kind']}.py",
                       f"loop_{cell.traffic['kind']}")
    with jax.default_matmul_precision(cell.cfg["matmul_precision"]):
        return loop.run(cell)


class View:
    """What a per-layer metric reader sees."""

    def __init__(self, cell: Cell, result: Result):
        self.cell = cell
        self.record = result.record
        self.trace = cell.reduced_trace
        self.chips = len(cell.devices)

    @functools.cached_property
    def peak(self) -> dict:
        return work.peaks(self.cell.devices[0].device_kind)

    def ops(self, batch: int) -> list[dict]:
        return work.forward_ops(self.cell.ref, self.cell.cfg, batch)


def metrics_line(cell: Cell, result: Result, bench: dict) -> dict:
    name = cell.workload["name"]
    out = {}
    if not cell.trace:
        values = {**result.e2e, "setup_s": cell.setup_s}
        for m in bench["end_to_end"]:
            if "workloads" not in m or name in m["workloads"]:
                out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        return out
    view = View(cell, result)
    for m in bench["per_layer"]:
        if name not in m["workloads"]:
            continue
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"metric_{m['name']}")
        value = reader.read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def judge(result: Result, lim: dict[str, float]) -> tuple[bool, dict]:
    checks = {}
    for k, v in result.checks.items():
        if k not in lim:
            raise KeyError(f"no limit for {k!r}")
        checks[k] = {"value": v, "limit": lim[k]}
    correct = (set(checks) == set(lim)
               and all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                       for c in checks.values()))
    return correct, checks


def main(argv: list[str] | None, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = benchmark()
    wl = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if wl is None:
        print(f"no workload {args.workload!r}", file=sys.stderr)
        return 2
    try:
        devices = require_accelerator(wl["chips"])
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    cell = cell_for(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_start, devices, bench)
    lim = limits(args.workload)
    result = run_loop(cell)
    correct, checks = judge(result, lim)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": cell.memory_peak_bytes}
    line = {"correct": correct, "attempted": result.attempted,
            "failed": result.failed,
            "metrics": metrics_line(cell, result, bench), "device": device}
    if cell.trace:
        device["busy_s"] = cell.reduced_trace["busy_s"]
        device["window_s"] = cell.reduced_trace["window_s"]
        line["breakdown"] = xtrace.breakdown(cell.reduced_trace)
        families = list(xtrace.by_family(cell.reduced_trace).items())[:20]
        print(f"device seconds by operation family: {families!r}; all "
              f"operations {sum(cell.reduced_trace['ops'].values())} s, busy "
              f"{cell.reduced_trace['busy_s']} s")
    line["checks"] = checks
    print(f"bench: set-up {cell.setup_s} s, window {result.record['window_s']}"
          f" s, compilations in the window {cell.window_compiles}, trace "
          f"reduced in {(cell.reduced_trace or {}).get('reduce_s')} s, device "
          f"memory {devices[0].memory_stats()}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
