"""Offline traffic: back-to-back batches, a closed loop (MLPerf Offline).

Parameters (``traffic/<name>.json``): ``batch`` images per request,
``queued_s`` seconds of device work to keep dispatched and not yet waited
for, and ``pool`` distinct batches made from the seed and sent in turn.

Every request goes through ``ConvServeEngine.infer``.  Set-up times the
pool's batches back to back and keeps ``ceil(queued_s / batch time)``
requests in flight (at least 2), so that the device has work while the
host learns late that a request finished: the TPU runtime sometimes
reports a finished program more than a second after the device went idle.
The window runs until ``cell.seconds`` have passed and then waits for
what is in flight; ``images_per_s`` is the images of all requests over the
whole window.  Every output is then compared with the reference
(``checks.logits``).
"""

from __future__ import annotations

import collections
import math
import sys
import time

import jax
import numpy as np

import checks
from harness import Result, span


def engine_for(cell, params):
    """The serving engine, one per process, so that a calibration over many
    seeds compiles once (``infer`` passes the weights as an argument)."""
    from repro.serve import ConvServeEngine

    engine = cell.cache.get("engine")
    if engine is None:
        engine = cell.cache["engine"] = ConvServeEngine(
            cell.forward, params)
    engine.params = params
    return engine


def closed_loop(cell, dispatch, in_flight: int) -> tuple[list, object]:
    """The measured window of a closed loop: ``dispatch(i)`` sends the i-th
    request and returns what to wait for; at most ``in_flight`` are sent
    and not yet waited for.  Sends until ``cell.seconds`` have passed, then
    waits for the rest.  Returns (what each request returned, window)."""
    outs, queue = [], collections.deque()
    with cell.window() as win:
        while not win.over():
            with span("dispatch"):
                y = dispatch(len(outs))
            outs.append(y)
            queue.append(y)
            if len(queue) >= in_flight:
                with span("wait"):
                    jax.block_until_ready(queue.popleft())
        with span("drain"):
            jax.block_until_ready(list(queue))
    return outs, win


def run(cell) -> Result:
    t = cell.traffic
    B, pool = t["batch"], t["pool"]
    params = cell.weights()
    images = cell.images((pool, B))
    batches = [images[p] for p in range(pool)]
    engine = engine_for(cell, params)
    jax.block_until_ready(engine.infer(batches[0]))     # compiles the one shape
    t0 = time.perf_counter()
    jax.block_until_ready([engine.infer(b) for b in batches])
    batch_s = (time.perf_counter() - t0) / pool
    in_flight = max(2, math.ceil(t["queued_s"] / batch_s))
    print(f"offline: a batch takes {batch_s} s, {in_flight} in flight",
          file=sys.stderr)

    outs, win = closed_loop(
        cell, lambda i: engine.infer(batches[i % pool]), in_flight)
    n = len(outs)
    engine.params = params = None
    found, control = checks.logits(cell, outs, np.arange(n) % pool, images)
    return Result(
        attempted=n, failed=int(found["missing"]),
        e2e={"images_per_s": n * B / win.seconds},
        record={"window_s": win.seconds, "requests": n, "images": n * B,
                "batch": B, "in_flight": in_flight,
                "images_per_s": n * B / win.seconds},
        checks=found, control=control)
