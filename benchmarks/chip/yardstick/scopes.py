"""Device self time by the program's named scopes, from a profiler trace.

Each device plane of an ``.xplane.pb`` keeps one event-metadata entry per
op, and its ``tf_op`` stat is the op's name path, with the program's scopes
in it (``repro.obs``).  ``jax.profiler.ProfileData`` does not expose those
stats, so they are read from the protobuf's wire format here:

    XSpace.planes = 1: XPlane
    XPlane.name = 2, .event_metadata = 4 (map int64 -> XEventMetadata),
          .stat_metadata = 5 (map int64 -> XStatMetadata)
    XEventMetadata.name = 2, .stats = 5: XStat
    XStatMetadata.name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (a stat name)

The window's device ops are ``trace``'s own (``trace._events``), clipped to
the window's span; each op's self time (``trace.self_times``) goes to the
innermost scope in its ``tf_op``, or to no scope.
"""
from __future__ import annotations

import collections
import sys
from typing import Dict, Optional

from . import trace as T


def _varint(buf, i: int):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, v


def _map_entry(buf):
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def tf_ops(path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {op name: tf_op}} from a trace's event metadata."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                k, meta = _map_entry(v)
                stat_names[k] = next((_text(x) for g, x in _fields(meta)
                                      if g == 2), "")
        if not name.startswith("/device:") or name.startswith("/device:CPU"):
            continue
        ops = out.setdefault(name, {})
        for entry in events:
            _, meta = _map_entry(entry)
            op, tf_op = "", None
            for f, v in _fields(meta):
                if f == 2:
                    op = _text(v)
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:
                        tf_op = _text(stat[5])
                    elif 7 in stat:
                        tf_op = stat_names.get(stat[7], "")
            if tf_op is not None:
                ops.setdefault(op, tf_op)
    return out


def attribute(path: str, window: Optional[str] = "bench.window"):
    """Device self seconds per scope inside the ``window`` span (the whole
    trace where there is none), averaged over the device planes with work:
    ``busy_s``, ``scopes`` (every name of ``SCOPES``), ``unscoped_s`` and
    ``ops``, a list of (op, tf_op, scope or None, seconds).  None where the
    program under test has no scopes to read."""
    try:
        from repro.obs import SCOPES, innermost
    except ImportError:
        print("scopes: the program names no scopes (no repro.obs)",
              file=sys.stderr, flush=True)
        return None
    devices, spans = T._events(path)
    if not devices:
        raise ValueError(f"{path}: no device operations in the trace")
    bounds = [(s, e) for n, s, e in spans if n == window]
    if bounds:
        w0, w1 = min(s for s, _ in bounds), max(e for _, e in bounds)
    else:
        w0, w1 = float("-inf"), float("inf")
    names = tf_ops(path)
    scopes = dict.fromkeys(SCOPES, 0.0)
    unscoped = busy = 0.0
    ops: Dict[tuple, float] = collections.defaultdict(float)
    for plane, events in devices.items():
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in events
                   if e > w0 and s < w1]
        busy += sum(e - s for s, e in T._merge(
            [(s, e) for _, s, e in clipped])) * 1e-9
        table = names.get(plane, {})
        for op, secs in T.self_times(clipped).items():
            tf_op = table.get(op, "")
            scope = innermost(tf_op)
            ops[(op, tf_op, scope)] += secs
            if scope is None:
                unscoped += secs
            else:
                scopes[scope] += secs
    k = len(devices)
    return {"busy_s": busy / k, "unscoped_s": unscoped / k,
            "scopes": {s: v / k for s, v in scopes.items()},
            "ops": [(op, tf_op, scope, secs / k)
                    for (op, tf_op, scope), secs in ops.items()]}


def trace_path(cell) -> str:
    """The traced window's ``.xplane.pb``, where ``run.py`` lays it out:
    ``.out/<cell>/trace``."""
    from . import spec
    return T.find_xplane(str(spec.HERE / ".out" / cell.name / "trace"))


def of_run(out, cell, metric: str) -> Optional[dict]:
    """:func:`attribute` of a traced run's window (kept in the run's
    ``out.counters`` for the next reader), or None, logged under
    ``metric``'s name, where no device op carries a scope."""
    if not out.trace:
        return None
    if "scopes" not in out.counters:
        out.counters["scopes"] = attribute(trace_path(cell), "bench.window")
    got = out.counters["scopes"]
    if got is not None and not any(got["scopes"].values()):
        print(f"{metric}: no device op in the window carries a program "
              f"scope", file=sys.stderr, flush=True)
        return None
    return got


def scope_ms(out, cell, scope: str, metric: str) -> Optional[float]:
    """Milliseconds of device self time in ``scope`` per traced step."""
    got = of_run(out, cell, metric)
    if got is None:
        return None
    return 1e3 * got["scopes"][scope] / cell.traffic["trace_steps"]
