"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the device
operations that took most time, and the longest device-idle gaps named by
what the host was doing in them.

Device operations are the events of each device plane's ``XLA Ops`` line;
busy time is the union of their intervals.  Host activity is the
benchmark's own spans: ``jax.profiler.TraceAnnotation`` events whose name
starts with ``bench.`` on the host planes.  Both sit on the trace's one
clock.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
TOP = 10


def span(name: str):
    """A host span on the profiler's clock (a no-op while not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def start(log_dir: str) -> None:
    """Start tracing into a fresh ``log_dir``.  Python function calls and
    the runtime's verbose host events are not traced: they would swamp the
    host planes and slow the host many times over."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def short_name(hlo: str) -> str:
    """An XLA op event's HLO text without layouts, tuple indices and
    operands: ``%fusion.825 = (f32[8,5,3,1024], ...) fusion``."""
    s = re.sub(r"/\*index=\d+\*/", "", re.sub(r"\{[^{}]*\}", "", hlo))
    m = re.match(r"(%\S+ = .*?\)?\s*[\w-]+)\(", s)
    return (m.group(1) if m else s)[:160]


def self_times(ops: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds per op name, less the time of the ops nested inside it (a
    while loop's body runs on the same line, inside the loop's event)."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: list = []                   # [name, end, self_ns]
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][1]:
            done = stack.pop()
            out[done[0]] += done[2] * 1e-9
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([n, e, e - s])
    for done in stack:
        out[done[0]] += done[2] * 1e-9
    return out


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _events(path: str):
    """(device ops per device plane, host spans) as (name, start_ns,
    end_ns) tuples."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def reduce(path: str, window: Optional[str] = None) -> dict:
    """Busy seconds per chip (averaged over the device planes with work),
    the traced window's seconds, the top device operations by summed self
    time and the longest idle gaps by host span.

    ``window`` names a host span that bounds the window; without it the
    window runs from the first device operation or benchmark span to the
    end of the last one."""
    devices, spans = _events(path)
    if not devices:
        raise ValueError(f"{path}: no device operations in the trace")
    bounds = [(s, e) for n, s, e in spans if n == window] if window else []
    if bounds:
        w0, w1 = min(s for s, _ in bounds), max(e for _, e in bounds)
    else:
        every = [iv for ops in devices.values() for iv in ops] + spans
        w0 = min(s for _, s, _ in every)
        w1 = max(e for _, _, e in every)
    by_op: Dict[str, float] = collections.defaultdict(float)
    busy_ns: List[float] = []
    gaps: List[Tuple[float, float]] = []
    for ops in devices.values():
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in ops
                   if e > w0 and s < w1]
        for n, secs in self_times(clipped).items():
            by_op[short_name(n)] += secs
        merged = _merge([(s, e) for _, s, e in clipped])
        busy_ns.append(sum(e - s for s, e in merged))
        edge = w0
        for s, e in merged:
            if s > edge:
                gaps.append((edge, s))
            edge = max(edge, e)
        if w1 > edge:
            gaps.append((edge, w1))
    host = [(n, s, e) for n, s, e in spans if n != window]
    named = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        best, most = "host: no benchmark span", 0.0
        for n, s, e in host:
            ov = min(e, g1) - max(s, g0)
            if ov > most:
                best, most = n, ov
        named.append([best, (g1 - g0) * 1e-9])
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
            "window_s": (w1 - w0) * 1e-9,
            "device_ops": [[n, s] for n, s in top_ops],
            "idle_gaps": named}
