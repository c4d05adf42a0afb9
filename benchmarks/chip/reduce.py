"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

* the window: the host span ``bench.window`` that the harness opens and
  closes around the timed steps;
* device busy time: the union of the intervals in which an operation ran
  on a chip (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, or its
  ``XLA Modules`` line where a plane has no op line), clipped to the
  window and averaged over the chips;
* per-program device time: the summed durations of each program's events
  on the ``XLA Modules`` line, averaged over the chips;
* the longest idle gaps inside the window, each named by the innermost
  host span of the benchmark (``serve.*``, ``train.*``) running at its
  middle, and the program that ran last before it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_HOST_SPAN = re.compile(r"^(serve|train)\.")

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals; returns them sorted."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi) between merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


_FINGERPRINT = re.compile(r"\(\d+\)$")


def _events(line):
    """(name, start, end) of a line's events; a program's name loses the
    fingerprint the runtime appends, so that programs group by name."""
    return [(_FINGERPRINT.sub("", e.name), e.start_ns,
             e.start_ns + e.duration_ns) for e in line.events]


def load(path: str):
    """(window, host spans, device planes) of one xplane file; each device
    plane is {"ops": [...], "modules": [...]} of (name, start, end) in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, spans, devices = None, [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, a, b in _events(line):
                    if name == WINDOW_SPAN:
                        window = (a, b)
                    elif _HOST_SPAN.match(name):
                        spans.append((name, a, b))
        elif _DEVICE.match(plane.name):
            lines = {line.name: _events(line) for line in plane.lines}
            devices[plane.name] = {"ops": lines.get("XLA Ops", []),
                                   "modules": lines.get("XLA Modules", [])}
    return window, spans, devices


def reduce_events(window: Interval, spans, devices: Dict,
                  n_devices: int, top: int = 10) -> Dict:
    lo, hi = window
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    busy_total, programs, all_gaps = 0.0, {}, []
    for name in sorted(devices)[:n_devices]:
        dev = devices[name]
        ops = dev["ops"] or dev["modules"]
        busy = union(clip([(a, b) for _, a, b in ops], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        for prog, a, b in dev["modules"]:
            seg = clip([(a, b)], lo, hi)
            if seg:
                programs[prog] = programs.get(prog, 0.0) + (seg[0][1]
                                                            - seg[0][0])
        if name == sorted(devices)[0]:
            mods = sorted((a, b, prog) for prog, a, b in dev["modules"])
            for g0, g1 in gaps(busy, lo, hi):
                mid = 0.5 * (g0 + g1)
                inner = [(b - a, s) for s, a, b in spans if a <= mid < b]
                host = min(inner)[1] if inner else "host"
                before = [p for a, b, p in mods if b <= g0]
                after = before[-1] if before else "window start"
                all_gaps.append((f"{host} after {after}", g1 - g0))
    n = min(n_devices, len(devices))
    programs = {k: v / n * 1e-9 for k, v in programs.items()}
    all_gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / n * 1e-9,
        "programs": programs,
        "top_programs": [[k, v] for k, v in sorted(
            programs.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * 1e-9] for k, v in all_gaps[:top]],
    }


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce_trace(trace_dir: str, n_devices: int) -> Dict:
    window, spans, devices = load(find_xplane(trace_dir))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return reduce_events(window, spans, devices, n_devices)
