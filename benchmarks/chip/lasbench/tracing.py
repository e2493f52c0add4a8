"""From a profiler trace to busy time, idle share and a breakdown.

``capture`` runs a block under ``jax.profiler`` and keeps a compact list
of events ``[plane, line, name, start_ns, duration_ns]``: the device
operations (the ``XLA Ops`` line of each TPU plane) and the harness's own
host spans (``lasbench.*``), which sit on the same clock. ``reduce``
turns that list into the numbers the metric readers take; it is checked
against a recorded trace in the tests.
"""

from __future__ import annotations

import contextlib
import glob
import json
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "lasbench."
CONTAINERS = ("%while", "%conditional", "%call")


def events_from_xplane(path: str) -> list:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    out.append([plane.name, line.name, ev.name,
                                int(ev.start_ns), int(ev.duration_ns)])
    return out


@contextlib.contextmanager
def capture(log_dir: str, into: list):
    """Trace the block; append its compact events to ``into``."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    for path in glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True):
        into.extend(events_from_xplane(path))
    shutil.rmtree(log_dir, ignore_errors=True)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _overlap(merged, s, e) -> int:
    return sum(max(0, min(e, b) - max(s, a)) for a, b in merged)


def reduce(events: list, devices: int) -> dict:
    """Window, busy time and breakdown of one traced window.

    The window is the ``lasbench.window`` span. Busy time is the union of
    the device operations' intervals inside it, per TPU plane; ``busy_s``
    is its mean over the ``devices`` planes used and ``busy_max_s`` the
    busiest. ``calls`` lists each ``lasbench.call`` span as (seconds,
    device-busy seconds inside it on the busiest plane)."""
    spans = [(n, s, s + d) for p, l, n, s, d in events
             if n.startswith(SPAN_PREFIX)]
    windows = [sp for sp in spans if sp[0] == SPAN_PREFIX + "window"]
    if not windows:
        raise ValueError("trace holds no lasbench.window span")
    _, w0, w1 = windows[0]
    ops = {}
    for plane, line, name, s, d in events:
        if DEVICE_PLANE.match(plane) and line == OPS_LINE:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                ops.setdefault(plane, []).append((a, b, name))
    planes = sorted(ops)[:devices] if ops else []
    merged = {p: _union([(a, b) for a, b, _ in ops[p]]) for p in planes}
    busy = {p: sum(b - a for a, b in merged[p]) for p in planes}
    window_ns = w1 - w0
    top = max(busy, key=busy.get) if busy else None
    calls = [(e - s, _overlap(merged[top], s, e) if top else 0)
             for n, s, e in spans if n == SPAN_PREFIX + "call"]

    op_time: dict = {}
    for a, b, name in (ops.get(top, []) if top else []):
        name = name.split(" = ")[0]           # HLO text -> "%fusion.12"
        if not name.startswith(CONTAINERS):   # loops hold the ops below
            op_time[name] = op_time.get(name, 0) + (b - a)
    device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]

    gaps = []
    if top:
        edges = [w0] + [x for iv in merged[top] for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _label(spans, (a + b) // 2)))
    gaps.sort(key=lambda g: -g[0])
    n_dev = max(len(planes), 1)
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy.values()) / n_dev * 1e-9,
        "busy_max_s": (busy[top] if top else 0) * 1e-9,
        "device_planes": planes,
        "calls": [(c * 1e-9, d * 1e-9) for c, d in calls],
        "breakdown": {
            "device_ops": [[n, t * 1e-9] for n, t in device_ops],
            "idle_gaps": [[lab, g * 1e-9] for g, lab in gaps[:10]],
        },
    }


def _label(spans, t) -> str:
    """The innermost harness span (other than the window) holding t."""
    inside = [(e - s, n) for n, s, e in spans
              if s <= t < e and n != SPAN_PREFIX + "window"]
    return min(inside)[1] if inside else "host outside harness spans"


def save_events(path: str, events: list):
    with open(path, "w") as f:
        json.dump(events, f)
