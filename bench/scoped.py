"""Device self time by convolution and stage, read from the trace's own
name-scope paths.

The program names each convolution ``conv_r{r}s{stride}_c{C}k{K}_h{H}w{W}``
(``repro.core.conv.conv_scope``); below it come the route the call took
(``direct``, ``winograd_fused_e2e``, ...) and then a stage of ``STAGES``,
or the stage ``epilogue`` straight below the conv (bias, batch norm,
ReLU).  The device plane keeps each operation's name-scope path in the
``tf_op`` stat of its event metadata, which ``jax.profiler.ProfileData``
does not show, so the file is read here with the message types of
``xplane.proto`` (the fields read, under their field numbers) built in code.

Each operation of a device's ``XLA Ops`` line (those ``xtrace.reduce_trace``
reads) is charged its self time: its interval less what operations inside
it cover, so a ``while`` and its body are not counted twice and the
charges add up to ``busy_s``.  An operation with no conv component in its
path is ``OUTSIDE``; one whose paths name two or more convs is ``SHARED``.

The harness deletes the window's trace before the per-layer readers run,
so ``traced`` takes a short traced window of its own, after the cell's:
``BATCHES`` batches of the served program, sent back to back.
"""

from __future__ import annotations

import collections
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

import xtrace

CONV_KEY = re.compile(r"conv_r(\d+)s(\d+)_c(\d+)k(\d+)_h(\d+)w(\d+)")
STAGES = ("tiles", "pad", "filter", "kernel", "crop", "epilogue")
OUTSIDE = ("outside", "", "")
SHARED = ("shared", "", "")
BATCHES = 16

_XPLANE = {  # message: [(field, number, type, repeated)], "Map<...>" a map
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "Map<XEventMetadata>", True),
               ("stat_metadata", 5, "Map<XStatMetadata>", True)],
    "XLine": [("name", 2, "string", False), ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False), ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XEventMetadata": [("display_name", 4, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
    "XStat": [("metadata_id", 1, "int64", False), ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
}


def _xspace_class():
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"string": F.TYPE_STRING, "int64": F.TYPE_INT64,
              "uint64": F.TYPE_UINT64}
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def field(msg, name, number, typ, repeated):
        f = msg.field.add(name=name, number=number, label=(
            F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL))
        if typ in scalar:
            f.type = scalar[typ]
        else:
            f.type, f.type_name = F.TYPE_MESSAGE, f".bench_xplane.{typ}"

    for name, fields in _XPLANE.items():
        msg = fd.message_type.add(name=name)
        for fname, number, typ, repeated in fields:
            if typ.startswith("Map<"):
                entry = msg.nested_type.add(name=f"{fname.title()}Entry")
                entry.options.map_entry = True
                field(entry, "key", 1, "int64", False)
                field(entry, "value", 2, typ[4:-1], False)
                typ = f"{name}.{entry.name}"
            field(msg, fname, number, typ, repeated)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def conv_key(r: int, stride: int, C: int, K: int, H: int, W: int) -> str:
    return f"conv_r{r}s{stride}_c{C}k{K}_h{H}w{W}"


def parse_key(key: str) -> tuple[int, ...] | None:
    """(r, stride, C, K, H, W) of a conv key; None for any other name."""
    m = CONV_KEY.fullmatch(key)
    return tuple(int(g) for g in m.groups()) if m else None


def scope_of(tf_op: str) -> tuple[str, str, str]:
    """(conv key, algorithm, stage) of one operation's ``tf_op``.

    ``tf_op`` is the operation's name-scope path, ``:`` and its type after
    it; the paths of operations that were merged are joined by ``;``.  No conv
    component anywhere gives ``OUTSIDE``, two or more different ones give
    ``SHARED``.  The algorithm is the component below the conv key unless
    that is a stage; the stage is the first component of ``STAGES`` below
    the key, short of the last (the primitive's own name).  Either may be
    ``""``.
    """
    paths = [p.split(":")[0].split("/") for p in tf_op.split(";")]
    keys = {c for p in paths for c in p if CONV_KEY.fullmatch(c)}
    if not keys:
        return OUTSIDE
    if len(keys) > 1:
        return SHARED
    (key,) = keys
    path = next(p for p in paths if key in p)
    below = path[path.index(key) + 1:-1]
    algorithm = below[0] if below and below[0] not in STAGES else ""
    stage = next((c for c in below if c in STAGES), "")
    return key, algorithm, stage


def self_ps(events: list[tuple[int, int, object]]) -> dict:
    """Self time of ``(start, end, label)`` intervals on one line, summed by
    label: each instant of the union is charged once, to the operation
    that started last among those running (the innermost, where they
    nest), so a ``while`` is charged only what its body does not cover and
    the labels' sum is the union."""
    out: dict = collections.defaultdict(int)
    stack: list[tuple[int, int, object]] = []
    t = 0

    def close(limit):
        nonlocal t
        while stack and stack[-1][1] <= limit:
            _, end, label = stack.pop()
            if end > t:
                out[label] += end - t
                t = end

    for ev in sorted(events, key=lambda e: (e[0], -e[1])):
        close(ev[0])
        if stack and ev[0] > t:
            out[stack[-1][2]] += ev[0] - t
        t = ev[0]
        stack.append(ev)
    close(float("inf"))
    return dict(out)


def device_ops(path: str) -> list[list[tuple[int, int, str, str]]]:
    """The operations of each device's ``XLA Ops`` line, as ``(start_ps,
    end_ps, hlo_name, tf_op)``; ``tf_op`` is ``""`` where the operation has
    none."""
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    out = []
    for plane in space.planes:
        if not xtrace.DEVICE_PLANE.match(plane.name):
            continue
        stat = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        for mid, m in plane.event_metadata.items():
            tf_op = next((s.str_value or stat.get(s.ref_value, "")
                          for s in m.stats
                          if stat.get(s.metadata_id) == "tf_op"), "")
            meta[mid] = (m.display_name, tf_op)
        for line in plane.lines:
            if line.name == xtrace.OPS_LINE:
                t0 = line.timestamp_ns * 1000
                out.append([(t0 + e.offset_ps, t0 + e.offset_ps + e.duration_ps,
                             *meta[e.metadata_id]) for e in line.events])
    return out


def scoped(path: str) -> dict[tuple[str, str, str], float]:
    """Device self seconds by (conv key, algorithm, stage), averaged over
    the devices, with ``OUTSIDE`` and ``SHARED`` among the keys: the
    values add up to ``busy_s``."""
    lines = device_ops(path)
    if not any(lines):
        raise ValueError(f"no device operations in {path}")
    scopes = {tf_op: scope_of(tf_op) for events in lines
              for *_, tf_op in events}
    total: dict = collections.defaultdict(int)
    for events in lines:
        for label, ps in self_ps([(s, e, scopes[tf_op])
                                  for s, e, _, tf_op in events]).items():
            total[label] += ps
    return {k: v * 1e-12 / len(lines) for k, v in sorted(total.items())}


def by_conv(scoped_s: dict) -> dict[str, dict[str, float]]:
    """Seconds per conv key (and ``outside``, ``shared``), split by stage,
    or by algorithm where an operation has no stage; largest first."""
    out: dict = collections.defaultdict(lambda: collections.defaultdict(float))
    for (key, algorithm, stage), t in scoped_s.items():
        out[key][stage or algorithm or "-"] += t
    return dict(sorted(((k, dict(v)) for k, v in out.items()),
                       key=lambda kv: -sum(kv[1].values())))


def conv_table(scoped_s: dict, busy_s: float, batches: int) -> str:
    """Device self seconds per batch of each conv key, ``outside`` and
    ``shared``, each with its share of busy time and split by stage."""
    rows = by_conv(scoped_s)
    cells = []
    for key, stages in rows.items():
        total = sum(stages.values())
        split = ", ".join(f"{k} {v / batches!r}" for k, v in
                          sorted(stages.items(), key=lambda kv: -kv[1]))
        cells.append(f"{key} {total / batches!r} s ({100 * total / busy_s!r} %"
                     f" of busy; {split})")
    added = sum(sum(stages.values()) for stages in rows.values())
    return (f"device self seconds per batch by conv, {batches} batches: "
            + "; ".join(cells) + f"; all of them {added / busy_s!r} of busy")


def traced(view) -> dict:
    """``{"scoped", "busy_s", "batches"}`` of a traced window of ``BATCHES``
    batches of the cell's served program, taken once per cell after its
    window.  Prints the per-conv table, what the window cost and the
    compilations it needed (the program is the window's: none past the
    images) to stderr."""
    cell = view.cell
    got = cell.cache.get("scoped")
    if got is None:
        got = cell.cache["scoped"] = _trace_batches(cell, view.record["batch"])
        print(conv_table(got["scoped"], got["busy_s"], got["batches"])
              + f"; seconds: set-up {got['setup_s']!r}, traced batches "
              f"{got['trace_s']!r}, reduction {got['reduce_s']!r}; "
              f"{got['compiles']} compilations", file=sys.stderr)
    return got


def _trace_batches(cell, batch: int) -> dict:
    t0 = time.perf_counter()
    engine = cell.cache["engine"]
    # The loop lets go of the weights after its window; the program is the
    # window's (same shapes, same precision), so nothing compiles again.
    engine.params = cell.weights()
    x = cell.images((batch,))
    compiles = []
    listener = (lambda event, *_a, **_k: compiles.append(event)
                if "backend_compile" in event else None)
    jax.monitoring.register_event_duration_secs_listener(listener)
    tmp = tempfile.mkdtemp(prefix="bench_scoped_")
    try:
        with jax.default_matmul_precision(cell.cfg["matmul_precision"]):
            jax.block_until_ready(engine.infer(x))
            t1 = time.perf_counter()
            jax.profiler.start_trace(tmp)
            jax.block_until_ready([engine.infer(x) for _ in range(BATCHES)])
            jax.profiler.stop_trace()
        t2 = time.perf_counter()
        path = xtrace.find_xplane(tmp)
        out = {"scoped": scoped(path),
               "busy_s": xtrace.reduce_trace(path)["busy_s"],
               "batches": BATCHES, "compiles": len(compiles),
               "setup_s": t1 - t0, "trace_s": t2 - t1,
               "reduce_s": time.perf_counter() - t2}
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
        shutil.rmtree(tmp, ignore_errors=True)
        engine.params = None
    return out
