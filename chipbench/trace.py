"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

``jax.profiler.ProfileData`` reads the file with nothing but JAX.  Device
planes are ``/device:TPU:<n>``; the operations that ran on a device are
the events of its ``XLA Ops`` line.  Host threads are the lines of the
``/host:CPU`` plane, where the benchmark's own ``TraceAnnotation`` spans
(``bench.*``) sit beside the runtime's.  The traced window is the
``bench.window`` span.  All start times share one clock, in ns.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# Its stats give the profiling session's start and stop (ns); event times
# count from the start.
SESSION_PLANE = "Task Environment"
WINDOW = "bench.window"
TOP = 10
# Ops that contain other ops on the same line: their time is their
# children's, so they count for busy time and not for time per op.
CONTAINERS = {"while", "conditional", "call"}

# An op event is named by its HLO text: ``%<name> = <shape> <opcode>(...``.
_OP = re.compile(r"%([^\s=]+) = (.*?)[\]})] ([a-z][\w\-]*)\(")
_SUFFIX = re.compile(r"(\.\d+|\.clone)+$")
_ARRAY = re.compile(r"^([a-z]+\d*)\[([\d,]*)\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
# The custom-call target of a Pallas (Mosaic) kernel on a TPU; XLA's own
# custom calls (``ConcatBitcast`` ...) are ops, not kernels.
KERNEL_TARGET = "tpu_custom_call"


def parse_op(text: str) -> dict:
    """Base name, opcode, output dtype and element count (for an array
    result), and whether it is a kernel, of an op event named by its HLO
    text."""
    m = _OP.match(text)
    if not m:
        return {"name": text, "opcode": None, "dtype": None, "elems": None,
                "kernel": False}
    shape = _ARRAY.match(m.group(2) + "]")
    elems = None
    if shape:
        elems = 1
        for d in filter(None, shape.group(2).split(",")):
            elems *= int(d)
    target = _TARGET.search(text)
    return {"name": _SUFFIX.sub("", m.group(1)), "opcode": m.group(3),
            "dtype": shape.group(1) if shape else None, "elems": elems,
            "kernel": bool(target) and target.group(1) == KERNEL_TARGET}


def find_xplane(directory: str) -> str:
    """The newest ``.xplane.pb`` under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
            for ev in line.events]


def load(path: str) -> dict:
    """Device op events per device and host events per thread."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, session = {}, {}, None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host[line.name] = _events(line)
        elif plane.name == SESSION_PLANE:
            st = dict(plane.stats)
            session = (0.0, float(st["profile_stop_time"])
                       - float(st["profile_start_time"]))
    return {"devices": devices, "host": host, "session": session}


def window_of(t: dict) -> Tuple[float, float]:
    """The ``bench.window`` span; where the host tracer dropped it, the
    whole profiling session, which starts just before the window and
    stops just after it."""
    spans = [(s, s + d) for evs in t["host"].values()
             for name, s, d in evs if name == WINDOW]
    if spans:
        return max(spans, key=lambda w: w[1] - w[0])
    if t["session"] is None:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    return t["session"]


def _label(host: dict, lo: float, hi: float) -> str:
    """What the host was doing in ``[lo, hi)``: the innermost ``bench.*``
    span at its middle, and the host event that overlaps it most."""
    mid = 0.5 * (lo + hi)
    bench, best = None, None
    for thread, evs in host.items():
        for name, s, d in evs:
            e = s + d
            if e <= lo or s >= hi or name == WINDOW:
                continue
            if name.startswith("bench."):
                if s <= mid < e and (bench is None or d < bench[1]):
                    bench = (name, d)
                continue
            ov = min(e, hi) - max(s, lo)
            key = (ov, -d)
            if best is None or key > best[0]:
                best = (key, f"{thread}: {name}")
    parts = [p for p in ((bench or (None,))[0], best and best[1]) if p]
    return " / ".join(parts) if parts else "no host event"


def reduce(path: str) -> dict:
    """Busy and idle time of the devices in the traced window, time per
    device operation, and the longest idle gaps with what the host did.

    Times are seconds.  ``ops`` sums each operation's device time over
    the devices, by base name (``wilson_hop_eo.13`` counts as
    ``wilson_hop_eo``); ``kernels`` does the same for the Pallas kernels
    by name, output dtype and output size; ``busy_s`` is
    the union of op intervals, averaged over the devices that ran any.
    """
    t = load(path)
    lo, hi = window_of(t)
    window = (hi - lo) * 1e-9
    ops, calls, busy, first_gaps = {}, {}, [], None
    parsed = {}
    for dev, evs in sorted(t["devices"].items()):
        inside = [(n, s, d) for n, s, d in evs if s + d > lo and s < hi]
        if not inside:
            continue
        for n, s, d in inside:
            op = parsed.get(n) or parsed.setdefault(n, parse_op(n))
            if op["opcode"] in CONTAINERS:
                continue
            o = ops.setdefault(op["name"], [0.0, 0])
            o[0] += d * 1e-9
            o[1] += 1
            if op["kernel"]:
                key = (op["name"], op["dtype"], op["elems"])
                c = calls.setdefault(key, [0.0, 0])
                c[0] += d * 1e-9
                c[1] += 1
        u = clip(union((s, s + d) for _, s, d in inside), lo, hi)
        busy.append(sum(e - s for s, e in u) * 1e-9)
        if first_gaps is None:
            edges = [lo] + [x for iv in u for x in iv] + [hi]
            first_gaps = [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
    if not busy:
        raise ValueError("no device operation ran in the traced window")
    gaps = sorted(first_gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": window,
        "busy_s": sum(busy) / len(busy),
        "devices": len(busy),
        "ops": {n: {"s": v[0], "calls": v[1]} for n, v in ops.items()},
        "kernels": [{"name": k[0], "dtype": k[1], "elems": k[2],
                          "s": v[0], "calls": v[1]}
                         for k, v in sorted(calls.items(), key=str)],
        "device_ops": [[n, v[0]] for n, v in sorted(
            ops.items(), key=lambda kv: -kv[1][0])[:TOP]],
        "idle_gaps": [[_label(t["host"], a, b), (b - a) * 1e-9]
                      for a, b in gaps],
    }
