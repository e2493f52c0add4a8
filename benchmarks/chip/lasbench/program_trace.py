"""The engine's own spans and stages, read from a profiler trace.

The simulator marks each engine call with host spans ``lasana.*`` that
carry counters as stats (``call``, ``bytes``, ...), and its tick cascade
with ``jax.named_scope`` stages (``drive``, ``features``, ``heads``,
``update``, ``flush``) that XLA keeps in each operation's ``op_name``.

``capture`` runs a block under ``jax.profiler`` like ``tracing.capture``,
and keeps what that keeps plus the engine's spans: each event is
``[plane, line, name, start_ns, duration_ns, stats]``, where ``stats``
holds a span's counters or a device operation's ``stage``. ``reduce``
returns ``tracing.reduce``'s numbers, computed by it from the first five
fields, and adds ``spans`` and ``stages``; its idle gaps are labelled with
the innermost span of either kind, its device operations prefixed with
their stage. ``READERS`` reads the engine's per-layer metrics from it, with
the signature of a ``metrics/<name>.py`` reader; each reads ``None`` from a
trace of a program without the spans or scopes.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil

from lasbench import tracing

SPAN_PREFIX = "lasana."
STAGES = ("drive", "features", "heads", "update", "flush")
OTHER = "other"
MODULES_LINE = "XLA Modules"


def stage_of(op_name: str):
    """The innermost stage scope in an XLA ``op_name`` path, else None."""
    scopes = [s for s in op_name.split("/")[:-1] if s in STAGES]
    return scopes[-1] if scopes else None


HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)")
HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = .*?\b[a-z][a-z0-9-]*\("
                    r"%?([^\s,()]*)")
HLO_OP_NAME = re.compile(r"metadata=\{op_name=\"([^\"]*)\"")


def op_stages(hlo_texts) -> dict:
    """``{(module, op): stage}`` from optimized HLO texts (for instance
    ``NetworkEngine.compiled_hlo()``): a TPU trace names each operation as
    the text does, and the text keeps its ``op_name``. An operation the
    compiler added with no ``op_name`` (a layout copy, an async copy or
    slice) takes the stage of its first operand, whose data it moves."""
    out = {}
    for text in hlo_texts:
        lines = text.splitlines()
        module = HLO_MODULE.match(lines[0]).group(1) if lines else ""
        stage = {}
        for line in lines:        # operands are defined before their use
            m = HLO_OP.match(line)
            if not m:
                continue
            name = HLO_OP_NAME.search(line)
            stage[m.group(1)] = (stage_of(name.group(1)) if name
                                 else stage.get(m.group(2)))
        out.update({(module, op): st for op, st in stage.items() if st})
    return out


def _op_key(module_event: str, op_event: str):
    """(module, op) of a device operation: the ``XLA Modules`` event reads
    ``jit_sim(<fingerprint>)``, the ``XLA Ops`` event ``%fusion.3 = ...``
    (or ``%fusion.3``)."""
    return (module_event.split("(")[0],
            op_event.split(" = ")[0].lstrip("%"))


def events_from_xplane(path: str, stages: dict) -> list:
    """``tracing``'s compact events, a device operation named by its HLO
    name alone (``%fusion.3``), with a sixth field: the ``lasana.*``
    spans' stats, and each device operation's stage from ``stages``
    (``op_stages``); ``lasbench.*`` spans and unstaged operations get
    ``{}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            modules = sorted((int(ev.start_ns), ev.name)
                             for line in plane.lines
                             if line.name == MODULES_LINE
                             for ev in line.events)
            starts = [s for s, _ in modules]
            for line in plane.lines:
                if line.name != tracing.OPS_LINE:
                    continue
                for ev in line.events:
                    name = ev.name.split(" = ")[0]
                    i = bisect.bisect_right(starts, int(ev.start_ns)) - 1
                    stage = (stages.get(_op_key(modules[i][1], name))
                             if i >= 0 else None)
                    out.append([plane.name, line.name, name,
                                int(ev.start_ns), int(ev.duration_ns),
                                {"stage": stage} if stage else {}])
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    stats = dict(ev.stats)
                elif ev.name.startswith(tracing.SPAN_PREFIX):
                    stats = {}
                else:
                    continue
                out.append([plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns), stats])
    return out


@contextlib.contextmanager
def capture(log_dir: str, into: list, hlo_texts=()):
    """Trace the block; append its events, with stats, to ``into``. The
    stages come from ``hlo_texts``, the programs' optimized HLO."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    stages = op_stages(hlo_texts)
    for path in glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True):
        into.extend(events_from_xplane(path, stages))
    shutil.rmtree(log_dir, ignore_errors=True)


def _stats(event) -> dict:
    return event[5] if len(event) > 5 else {}


def reduce(events: list, devices: int) -> dict:
    """``tracing.reduce`` of the same trace, and besides:

    spans   per ``lasana.*`` span name: ``count``, ``total_s``, ``self_s``
            (less the time of the spans nested in it), ``busy_s`` (device
            busy inside it on the busiest plane) and ``bytes`` (the sum of
            its ``bytes`` counter)
    stages  device-busy seconds per stage on the busiest plane, and
            ``other`` for the busy time under no stage (containers such as
            ``%while`` count only where no operation inside them runs)
    """
    out = tracing.reduce([e[:5] for e in events], devices)
    w0, w1 = next((s, s + d) for _, _, n, s, d, *_ in events
                  if n == tracing.SPAN_PREFIX + "window")
    ops: dict = {}
    program = []
    for ev in events:
        plane, line, name, s, d = ev[:5]
        if tracing.DEVICE_PLANE.match(plane) and line == tracing.OPS_LINE:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                ops.setdefault(plane, []).append(
                    (a, b, name, _stats(ev).get("stage")))
        elif name.startswith(SPAN_PREFIX) and w0 <= s < w1:
            program.append((name, s, s + d, _stats(ev)))
    planes = sorted(ops)[:devices]
    merged = {p: tracing._union([(a, b) for a, b, *_ in ops[p]])
              for p in planes}
    busy = {p: sum(b - a for a, b in merged[p]) for p in planes}
    top = max(busy, key=busy.get) if busy else None
    top_ops = ops.get(top, [])
    top_merged = merged.get(top, [])

    spans: dict = {}
    for i, (n, s, e, st) in enumerate(program):
        nested = tracing._union([(a, b) for j, (_, a, b, _) in
                                 enumerate(program)
                                 if j != i and s <= a and b <= e])
        sp = spans.setdefault(n, {"count": 0, "total_s": 0.0, "self_s": 0.0,
                                  "busy_s": 0.0, "bytes": 0})
        sp["count"] += 1
        sp["total_s"] += (e - s) * 1e-9
        sp["self_s"] += (e - s - sum(b - a for a, b in nested)) * 1e-9
        sp["busy_s"] += tracing._overlap(top_merged, s, e) * 1e-9
        sp["bytes"] += int(st.get("bytes", 0))

    staged = {}
    for a, b, name, stage in top_ops:
        if stage and not name.startswith(tracing.CONTAINERS):
            staged.setdefault(stage, []).append((a, b))
    stages = {k: sum(b - a for a, b in tracing._union(v)) * 1e-9
              for k, v in staged.items()}
    covered = sum(b - a for a, b in
                  tracing._union([iv for v in staged.values() for iv in v]))
    if top:
        stages[OTHER] = (busy[top] - covered) * 1e-9

    op_time: dict = {}
    for a, b, name, stage in top_ops:
        name = name.split(" = ")[0]
        if not name.startswith(tracing.CONTAINERS):
            key = f"{stage}:{name}" if stage else name
            op_time[key] = op_time.get(key, 0) + (b - a)
    device_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]

    labelled = [(n, s, e) for n, s, e, _ in program] + [
        (n, s, s + d) for _, _, n, s, d, *_ in events
        if n.startswith(tracing.SPAN_PREFIX)]
    gaps = []
    if top:
        edges = [w0] + [x for iv in top_merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, tracing._label(labelled, (a + b) // 2)))
    gaps.sort(key=lambda g: -g[0])

    out["spans"] = spans
    out["stages"] = stages
    out["breakdown"] = {
        "device_ops": [[n, t * 1e-9] for n, t in device_ops],
        "idle_gaps": [[lab, g * 1e-9] for g, lab in gaps[:10]],
    }
    return out


def _host_ms_per_call(name: str):
    def read(ctx):
        sp = ctx["trace"].get("spans", {}).get(SPAN_PREFIX + name)
        if not sp or not sp["count"]:
            return None
        return (sp["total_s"] - sp["busy_s"]) / sp["count"] * 1e3
    return read


def _stimulus_gb_per_s(ctx):
    sp = ctx["trace"].get("spans", {}).get(SPAN_PREFIX + "stimulus")
    if not sp or not sp["total_s"] or not sp["bytes"]:
        return None
    return sp["bytes"] / sp["total_s"] * 1e-9


def _stage_us_per_tick(stage: str):
    def read(ctx):
        seconds = ctx["trace"].get("stages", {}).get(stage)
        ticks = ctx["counters"].get("ticks")
        if not seconds or not ticks:
            return None
        return seconds / ticks * 1e6
    return read


# name -> read(ctx), as ``metrics/<name>.py`` would define it
READERS = {
    "stimulus_ms_per_call": _host_ms_per_call("stimulus"),
    "fetch_ms_per_call": _host_ms_per_call("fetch"),
    "stimulus_gb_per_s": _stimulus_gb_per_s,
    "heads_us_per_tick": _stage_us_per_tick("heads"),
    "features_us_per_tick": _stage_us_per_tick("features"),
}
