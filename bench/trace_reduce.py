"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

* busy intervals per device: the union of the operations on the device's
  ``XLA Ops`` line, inside the window;
* device time per program (``XLA Modules`` line) and per operation
  (named ``program:op``; loops and conditionals are left to their bodies);
* idle gaps, each named by the innermost host span open at its middle:
  the ``repro.obs`` spans (``engine.*``, ``ring.*``), which
  ``obs.enable(jax_bridge=True)`` writes into the trace as annotations,
  and the benchmark's own (``bench.*``).

The window is the host annotation ``bench.window`` that the harness opens
around its timed joins.  Nothing here imports the program under test.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("bench.", "engine.", "ring.")
WINDOW_SPAN = "bench.window"
NO_SPAN = "(no span)"
# operations whose time is their body's: left out of the per-op breakdown
CONTROL_FLOW = ("%while", "%conditional", "%call")


@dataclasses.dataclass
class DeviceSummary:
    busy_s: float
    idle_by_span: Dict[str, float]     # idle seconds, by the host span open
    module_s: Dict[str, float]         # device seconds per program
    op_s: Dict[str, float]             # device seconds per "program:op"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    devices: Dict[int, DeviceSummary]
    spans: List[Tuple[str, float, float]]   # host spans in the window (name, start, end), ns

    def span_seconds(self, name: str) -> float:
        return sum(e - s for n, s, e in self.spans if n == name) * 1e-9


def _merge(intervals: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals, sorted."""
    if intervals.size == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    run_end = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > run_end[:-1]])
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    ends = run_end[np.concatenate([idx[1:] - 1, [len(iv) - 1]])]
    return np.stack([starts, ends], axis=1)


def _clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(intervals, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _name_gaps(spans, mids) -> List[str]:
    """For each time in ``mids`` (ascending), the innermost host span open
    at it.  ``spans`` are (name, start, end), sorted by start, nested as a
    thread's spans are."""
    names, stack, i = [], [], 0
    for t in mids:
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] <= spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        names.append(stack[-1][0] if stack else NO_SPAN)
    return names


def reduce_trace(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = []
    device_lines = defaultdict(dict)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    device_lines[int(m.group(1))][line.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} annotation in {path}")
    lo, hi = windows[0]
    spans = sorted(
        [sp for sp in spans if sp[2] > lo and sp[1] < hi], key=lambda sp: sp[1]
    )

    devices = {}
    for dev, lines in sorted(device_lines.items()):
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        mods = sorted(lines.get(MODULES_LINE, []), key=lambda ev: ev[1])
        mod_starts = [ev[1] for ev in mods]
        op_iv = np.array([(s, e) for _, s, e in ops], dtype=np.float64).reshape(-1, 2)
        busy = _merge(_clip(op_iv, lo, hi))
        edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        idle = defaultdict(float)
        for name, (g0, g1) in zip(_name_gaps(spans, gaps.mean(axis=1)), gaps):
            idle[name] += float(g1 - g0) * 1e-9
        module_s = defaultdict(float)
        for name, s, e in mods:
            if e > lo and s < hi:
                module_s[name] += (min(e, hi) - max(s, lo)) * 1e-9
        op_s = defaultdict(float)
        for name, s, e in ops:
            op = name.split(" = ", 1)[0]
            if e > lo and s < hi and not op.startswith(CONTROL_FLOW):
                k = bisect.bisect_right(mod_starts, s) - 1
                prog = mods[k][0] if k >= 0 and mods[k][2] >= e else "?"
                prog = prog.split("(", 1)[0]
                op_s[f"{prog}:{op}"] += (min(e, hi) - max(s, lo)) * 1e-9
        devices[dev] = DeviceSummary(
            busy_s=float((busy[:, 1] - busy[:, 0]).sum()) * 1e-9,
            idle_by_span=dict(idle),
            module_s=dict(module_s),
            op_s=dict(op_s),
        )
    return TraceSummary(window_s=(hi - lo) * 1e-9, devices=devices, spans=spans)


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
