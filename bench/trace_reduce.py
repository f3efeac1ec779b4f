"""Reduce a JAX profiler trace to the benchmark's device numbers.

Input: one ``.xplane.pb`` file written by ``jax.profiler``.  Device planes
are those named ``/device:<PLATFORM>:<n>``; on each, the ``XLA Ops`` line
holds one event per executed HLO operation and the ``XLA Modules`` line one
event per executed program.  Host planes carry the benchmark's own
``jax.profiler.TraceAnnotation`` spans (names starting with ``bench.``) on the
same clock, which is how each idle gap of the device is attributed to what the
host was doing.  The two clocks agree to about a millisecond (a v5e trace
shows the device about 1 ms ahead), so program executions are matched to the
window with a margin of ``MARGIN_NS``; busy time is clipped to the window.

``reduce_trace`` returns plain numbers:

- ``window_s``: length of the ``bench.window`` span (the measured window), or
  of the traced interval where that span is absent;
- ``busy_s``: union of the op intervals inside the window, averaged over the
  devices that ran anything;
- ``module_s`` / ``module_n``: summed device time and count of the program
  events whose name starts with ``module_prefix`` (the train step);
- ``device_ops``: the 10 ops with most device time, ``[name, seconds]``, an op
  named by its HLO instruction name (the trace's text up to `` = ``);
- ``idle_gaps``: the 10 longest gaps between ops, ``[span, seconds]``, named
  by the innermost ``bench.`` span that holds the gap's midpoint, or
  ``control plane`` where none does.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

_DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
CONTROL_PLANE = "control plane"
TOP = 10
MARGIN_NS = 10e6


def find_trace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _events(line):
    for e in line.events:
        start = float(e.start_ns)
        yield e.name.split(" = ")[0].lstrip("%"), start, start + float(e.duration_ns)


def read_planes(path: str):
    """(device lines, host spans) of the trace at ``path``.

    device lines: ``{plane name: {"ops": [(name, t0, t1)], "modules": [...]}}``;
    host spans: ``[(name, t0, t1)]`` for every ``bench.`` annotation.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name) and not plane.name.startswith("/device:CPU"):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                "ops": list(_events(lines[OPS_LINE])) if OPS_LINE in lines else [],
                "modules": (list(_events(lines[MODULES_LINE]))
                            if MODULES_LINE in lines else []),
            }
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(ev for ev in _events(ln) if ev[0].startswith("bench."))
    return devices, host


def reduce_trace(path: str, module_prefix: str = "jit_train_step") -> Dict:
    devices, host = read_planes(path)
    windows = [(a, b) for name, a, b in host if name == WINDOW_SPAN]
    used = {k: v for k, v in devices.items() if v["ops"]}
    if windows:
        lo, hi = windows[0][0], windows[-1][1]
    else:
        starts = [a for v in used.values() for _, a, _ in v["ops"]]
        ends = [b for v in used.values() for _, _, b in v["ops"]]
        lo, hi = (min(starts), max(ends)) if starts else (0.0, 0.0)
    spans = [(name, a, b) for name, a, b in host if name != WINDOW_SPAN]

    busy_ns = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    module_ns, module_n = 0.0, 0
    gaps: List[Tuple[float, float]] = []
    for dev in used.values():
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in dev["ops"] if b > lo and a < hi]
        merged = _union([(a, b) for _, a, b in ops])
        busy_ns += sum(b - a for a, b in merged)
        for name, a, b in ops:
            op_time[name] += b - a
        for name, a, b in dev["modules"]:
            if name.startswith(module_prefix) and a >= lo - MARGIN_NS and b <= hi + MARGIN_NS:
                module_ns += b - a
                module_n += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    def holder(t: float) -> str:
        inside = [(b - a, name) for name, a, b in spans if a <= t <= b]
        return min(inside)[1] if inside else CONTROL_PLANE

    n_dev = max(len(used), 1)
    longest = sorted(gaps, key=lambda g: g[1] - g[0], reverse=True)[:TOP]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "devices": len(used),
        "module_s": module_ns / 1e9,
        "module_n": module_n,
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[holder((a + b) / 2), (b - a) / 1e9] for a, b in longest],
    }


def reduce_dir(log_dir: str, module_prefix: str = "jit_train_step") -> Optional[Dict]:
    """``reduce_trace`` of the newest trace under ``log_dir``."""
    return reduce_trace(find_trace(log_dir), module_prefix)
