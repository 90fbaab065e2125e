"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle time.

Only ``jax.profiler.ProfileData`` reads the file.  A device is a plane named
``/device:<KIND>:<n>``; its operations are the events of its ``XLA Ops``
line.  What comes out:

* ``busy_s``: the union of the operation intervals of each device, in
  seconds, averaged over the devices;
* ``ops``: the seconds of each operation, named by its HLO instruction, summed over its events and
  averaged over the devices, with ``op_calls`` the number of its events;
* ``gaps``: the ``TOP_GAPS`` longest idle intervals between the busy
  intervals of the first device, longest first, each with the host span
  (an event of a host plane whose name starts with ``bench.``) that
  overlaps it most, or ``none``.
"""

from __future__ import annotations

import collections
import glob
import os
import re

from jax.profiler import ProfileData

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
# An event of the ops line is named by its HLO text, "%fusion.3 = f32[...] ...".
HLO_NAME = re.compile(r"^%?([^\s=]+)")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
TOP_GAPS = 10


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` that a ``jax.profiler.trace`` wrote."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under {trace_dir}")
    return found[0]


def op_name(event_name: str) -> str:
    """The HLO instruction's name: ``wino_fused_e2e.1``, ``fusion.3``."""
    m = HLO_NAME.match(event_name)
    return m.group(1) if m else event_name


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _overlap(a0, a1, b0, b1) -> int:
    return max(0, min(a1, b1) - max(a0, b0))


def reduce_trace(path: str) -> dict:
    data = ProfileData.from_file(path)
    devices, host_spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(op_name(ev.name), ev.start_ns, ev.end_ns)
                                    for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host_spans.append((ev.name, ev.start_ns, ev.end_ns))
    if not devices or not any(devices):
        raise ValueError(f"no device operations in {path}")
    n = len(devices)
    busy_ns = 0
    ops: dict[str, float] = collections.defaultdict(float)
    calls: dict[str, int] = collections.Counter()
    for events in devices:
        busy_ns += sum(e - s for s, e in _union([(s, e) for _, s, e in events]))
        for name, s, e in events:
            ops[name] += (e - s) / n
            calls[name] += 1
    first = _union([(s, e) for _, s, e in devices[0]])
    idle = sorted(((a, b) for (_, a), (b, _) in zip(first, first[1:])),
                  key=lambda g: g[0] - g[1])[:TOP_GAPS]
    gaps = []
    for a, b in idle:
        best = max(host_spans, key=lambda h: _overlap(a, b, h[1], h[2]),
                   default=None)
        label = (best[0] if best is not None
                 and _overlap(a, b, best[1], best[2]) > 0 else "none")
        gaps.append((label, (b - a) * 1e-9))
    return {"devices": n, "busy_s": busy_ns * 1e-9 / n,
            "ops": {k: v * 1e-9 for k, v in sorted(ops.items())},
            "op_calls": dict(sorted(calls.items())),
            "gaps": gaps}


def family(name: str) -> str:
    """An operation's family: its HLO name without the ``.N`` suffix."""
    return re.sub(r"\.\d+$", "", name)


def op_seconds(trace: dict, kernels) -> float:
    """Seconds of the operations of the families ``kernels``."""
    return sum(t for name, t in trace["ops"].items()
               if family(name) in kernels)


def instances(trace: dict, kernels) -> int:
    """Distinct operations (HLO instructions) of the families ``kernels``."""
    return sum(family(name) in kernels for name in trace["ops"])


def by_family(trace: dict) -> dict[str, float]:
    """Seconds per operation family: the HLO name without its ``.N``."""
    out: dict[str, float] = collections.defaultdict(float)
    for name, t in trace["ops"].items():
        out[family(name)] += t
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def breakdown(trace: dict, top: int = 10) -> dict:
    ops = sorted(trace["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [list(g) for g in trace["gaps"][:top]]}
