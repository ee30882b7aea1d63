"""From a profiler trace to the numbers the per-layer readers use.

The run traces a stretch of whole training steps inside a host annotation
(``TraceAnnotation``).  From the ``.xplane.pb`` the profiler writes, this
module keeps three kinds of events on one clock: the device's operations
(line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), and the host's Python
frames (on the thread lines of ``/host:CPU``, written by the profiler's
Python tracer as ``$<file>:<line> <function>``) with the annotations among
them.

Then, inside the annotation's span:

* busy time per device is the length of the union of its operations'
  intervals, averaged over the devices; the idle share is 1 - busy/span;
* a kernel's time is the sum of the durations of its operations, named by
  the HLO instruction (``%stacked_mean_linear_pallas.2 = ... custom-call``
  is kernel ``stacked_mean_linear_pallas``);
* each idle stretch of device 0 is put down to the innermost Python frame
  of the program's own functions that held the host then (a gap that
  spans several such frames is split between them).

"""

from __future__ import annotations

import bisect
import glob
import heapq
import re
from pathlib import Path
from typing import Dict, List, Tuple

_OP = re.compile(r"^%?([A-Za-z0-9_\-]+?)(?:\.\d+)?\s*=")
_FRAME = re.compile(r"^\$(\S+?):\d+ (\S+)$")


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    m = _OP.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def is_kernel(text: str) -> bool:
    return " custom-call(" in text


def load(path) -> dict:
    """Events of one trace file: {"devices": [[(start, end, text)]],
    "python": [(start, end, name)]}, times in nanoseconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, python = [], []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append(sorted(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                # Python frames ("$file:line function") and the annotations,
                # on whichever thread's line the profiler put them (a line
                # is named after its thread, e.g. "python3")
                python.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in line.events
                              if e.name.startswith(("$", "bench.")))
    return {"devices": devices, "python": sorted(python)}


def find_trace(directory) -> Path:
    files = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return Path(files[-1])


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals if e > t0 and s < t1]


def functions_of(files) -> frozenset:
    """``<file name>:<name>`` of every function and class the Python
    ``files`` define, and ``<file name>:<module>``: the frame names the
    Python tracer gives their code."""
    import ast

    out = set()
    for path in files:
        path = Path(path)
        out.add(f"{path.name}:<module>")
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                out.add(f"{path.name}:{node.name}")
    return frozenset(out)


def _own_frames(python, own) -> List[Tuple[float, float, str]]:
    """Frames of the program's own functions: ``own`` holds
    ``<file name>:<function>`` of each (the Python tracer records a frame's
    file by its name alone, so the function tells ``jax``'s
    ``profiler.py:wrapper`` from the program's ``profiler.py``)."""
    out = []
    for s, e, name in python:
        m = _FRAME.match(name)
        if m:
            key = f"{Path(m.group(1)).name}:{m.group(2)}"
            if key in own:
                out.append((s, e, key))
    return sorted(out)


def _frames_at(own, times) -> List[str]:
    """For each of the ascending ``times``, the innermost of the ``own``
    frames (sorted by start) that covers it: frames nest, so the one that
    began last.  One sweep, with a heap of the frames begun so far."""
    out, begun, i = [], [], 0
    for t in times:
        while i < len(own) and own[i][0] <= t:
            heapq.heappush(begun, (-own[i][0], own[i][1], own[i][2]))
            i += 1
        while begun and begun[0][1] < t:  # ended: ended for every later t too
            heapq.heappop(begun)
        out.append(begun[0][2] if begun else "(outside the program)")
    return out


def _idle_by_frame(idle, own) -> Dict[str, float]:
    """Seconds of the ``idle`` intervals (ascending, disjoint) under each
    innermost own frame: each interval is cut where an own frame begins or
    ends inside it."""
    cuts = sorted(x for s, e, _ in own for x in (s, e))
    pieces = []
    for a, b in idle:
        lo, hi = bisect.bisect_right(cuts, a), bisect.bisect_left(cuts, b)
        edges = [a, *cuts[lo:hi], b]
        pieces.extend((x, y) for x, y in zip(edges, edges[1:]) if y > x)
    out: Dict[str, float] = {}
    for (x, y), who in zip(pieces, _frames_at(own, [(x + y) / 2 for x, y in pieces])):
        out[who] = out.get(who, 0.0) + (y - x) * 1e-9
    return out


def reduce(events: dict, window: str, own=frozenset(), top: int = 10) -> dict:
    """Summary of the span of the host annotation named ``window``."""
    spans = [(s, e) for s, e, n in events["python"] if n == window]
    if not spans:
        raise ValueError(f"no annotation {window!r} in the trace")
    t0, t1 = spans[0]
    span_s = (t1 - t0) * 1e-9
    busy, kernels, calls, ops = [], {}, {}, {}
    for dev in events["devices"]:
        inside = [(s, e, x) for s, e, x in dev if e > t0 and s < t1]
        busy.append(sum(e - s for s, e in union(
            _clip([(s, e) for s, e, _ in inside], t0, t1))) * 1e-9)
        for s, e, text in inside:
            name = op_name(text)
            ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9
            if is_kernel(text):
                kernels[name] = kernels.get(name, 0.0) + (e - s) * 1e-9
                calls[name] = calls.get(name, 0) + 1
    n_dev = max(1, len(events["devices"]))
    gaps = {}
    if events["devices"]:
        dev0 = union(_clip([(s, e) for s, e, _ in events["devices"][0]], t0, t1))
        edges = [t0] + [x for iv in dev0 for x in iv] + [t1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        gaps = _idle_by_frame(idle, _own_frames(events["python"], own))
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": span_s,
        "busy_s": sum(busy) / n_dev,
        "devices": len(events["devices"]),
        "kernel_s": {k: v / n_dev for k, v in kernels.items()},
        "kernel_calls": {k: v / n_dev for k, v in calls.items()},
        "device_ops": [[k, v / n_dev] for k, v in by_time(ops)],
        "idle_gaps": [[k, v] for k, v in by_time(gaps)],
    }
