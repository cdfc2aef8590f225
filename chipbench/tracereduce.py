"""From the profiler's trace to the numbers the device metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
small neutral record: device operations (plane, name, start, duration,
the HLO text of the operation where the trace gives it) and host events
(thread, name, start, duration).  ``reduce`` turns that record into:

* busy seconds per device: the union of the intervals in which an
  operation ran, clipped to the traced window;
* the window: the harness's ``bench.window`` annotation;
* device seconds by program (``jit_<function>``), and the ten largest;
* the idle gaps inside the window, each named by the host event that
  covers its middle (the innermost one), summed by that name, largest
  ten;
* the events of named kernels, with their HLO text for byte counts.

The reduction is tested on a small recorded trace
(``chipbench/tests/trace_small.json``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional

import numpy as np

WINDOW_ANNOTATION = "bench.window"
# a device plane's line of operations, and of the programs that enclose
# them (counting both would count the same time twice); "Async XLA Ops"
# are copies that overlap the operations and are left out
OP_LINE, MODULE_LINE = "XLA Ops", "XLA Modules"


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def _module_name(name: str) -> str:
    """``jit__join_expand(1234)`` -> ``jit__join_expand``."""
    return name.split("(", 1)[0]


def extract(path: str, min_host_ns: float = 20_000.0) -> dict:
    """The neutral record of one trace file.

    Device operations come from each device plane's ``XLA Ops`` line:
    their short name, the program (``XLA Modules`` event) they ran in,
    start and duration in ns, and the full HLO text of custom calls (the
    kernels, whose bytes a roofline counts).  Host events shorter than
    ``min_host_ns`` are left out, apart from the harness's own."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((float(e.start_ns), float(e.duration_ns),
                           _module_name(e.name))
                          for e in lines.get(MODULE_LINE, []))
            starts = [m[0] for m in mods]
            for e in lines.get(OP_LINE, []):
                ts = float(e.start_ns)
                i = bisect.bisect_right(starts, ts) - 1
                module = (mods[i][2] if i >= 0 and ts <= mods[i][0]
                          + mods[i][1] else "")
                short, _, rest = e.name.partition(" = ")
                device.append({
                    "plane": plane.name, "module": module, "name": short,
                    "ts": ts, "dur": float(e.duration_ns),
                    "hlo": e.name if "custom-call(" in rest else ""})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if (e.duration_ns >= min_host_ns
                            or e.name.startswith("bench.")):
                        host.append({"thread": line.name, "name": e.name,
                                     "ts": float(e.start_ns),
                                     "dur": float(e.duration_ns)})
    return {"device": device, "host": host}


def _union(intervals: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def window_of(record: dict) -> Optional[tuple]:
    marks = [e for e in record["host"] if e["name"] == WINDOW_ANNOTATION]
    if not marks:
        return None
    e = max(marks, key=lambda e: e["dur"])
    return e["ts"], e["ts"] + e["dur"]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


class _HostCover:
    """Which host event covers an instant: the innermost (shortest) one,
    leaving out the window's own annotation."""

    def __init__(self, host: List[dict]):
        evs = [e for e in host if e["name"] != WINDOW_ANNOTATION]
        self.names = [e["name"] for e in evs]
        self.start = np.array([e["ts"] for e in evs], np.float64)
        self.end = self.start + np.array([e["dur"] for e in evs],
                                         np.float64)

    def name(self, t: float) -> str:
        hit = np.nonzero((self.start <= t) & (self.end >= t))[0]
        if not len(hit):
            return "(no host event)"
        return self.names[hit[np.argmin(self.end[hit] - self.start[hit])]]


def reduce(record: dict, top: int = 10) -> Optional[dict]:
    """The trace's numbers, or None when it holds no window or no device
    operation inside it."""
    win = window_of(record)
    if win is None:
        return None
    lo, hi = win
    by_plane: Dict[str, list] = defaultdict(list)
    by_name: Dict[str, float] = defaultdict(float)
    for e in record["device"]:
        a, b = _clip(e["ts"], e["ts"] + e["dur"], lo, hi)
        if b <= a:
            continue
        by_plane[e["plane"]].append((a, b))
        by_name[e["module"] or e["name"]] += (b - a) * 1e-9
    if not by_plane:
        return None
    cover = _HostCover(record["host"])
    busy = {}
    gaps: Dict[str, float] = defaultdict(float)
    for plane, iv in by_plane.items():
        merged = _union(iv)
        busy[plane] = sum(b - a for a, b in merged) * 1e-9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[cover.name((a + b) / 2)] += \
                    (b - a) * 1e-9 / len(by_plane)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy.values()) / len(busy)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "devices": len(busy),
        "device_ops": sorted(by_name.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1])[:top],
        "op_seconds": dict(by_name),
    }


def kernel_events(record: dict, pattern: str) -> List[dict]:
    """Device custom calls inside the window whose HLO text matches
    ``pattern``: whole events, as a roofline needs them."""
    rx = re.compile(pattern)
    win = window_of(record)
    return [e for e in record["device"]
            if e["hlo"] and rx.search(e["hlo"])
            and (win is None or win[0] <= e["ts"] < win[1])]


_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|f32|bf16|f16|s64|u64|"
                    r"f64)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


def _shapes(text: str) -> List[tuple]:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def hlo_shapes(hlo: str) -> tuple:
    """(result shapes, operand shapes) of one HLO instruction's text,
    each a list of (dtype, dims); attributes after the operand list
    (layout constraints and the like) are not read."""
    _, _, rhs = hlo.partition(" = ")
    opener = rhs.index("(", rhs.index("custom-call"))
    depth, close = 0, len(rhs)
    for i in range(opener, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[i], 0)
        if depth == 0:
            close = i
            break
    return _shapes(rhs[:opener]), _shapes(rhs[opener:close])


def shape_bytes(dt: str, dims: tuple) -> int:
    n = 1
    for d in dims:
        n *= d
    return n * _BYTES[dt]
