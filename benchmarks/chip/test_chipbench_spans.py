"""The engine's spans and stages in a trace: ``program_trace.reduce`` and
its readers by hand on a made-up trace, against ``tracing.reduce`` on the
recorded traces, and against a plain timeline count on a trace of
``snn-mnist.batch`` recorded on a TPU v5e with the engine's spans."""

import gzip
import json
import os
import re

import numpy as np
import pytest

from lasbench import program_trace, tracing

HARNESS = os.path.dirname(os.path.abspath(__file__))
HOST = "/host:CPU"
DEV = "/device:TPU:0"
OPS = "XLA Ops"
TICKS = 20


def _span(name, start, dur, **stats):
    return [HOST, "python3", name, start, dur, stats]


def _op(name, start, dur, stage=None):
    return [DEV, OPS, name, start, dur, {"stage": stage} if stage else {}]


def _made_up():
    return [
        _span("lasbench.window", 0, 1000),
        _span("lasbench.call", 100, 400),
        _span("lasana.run", 110, 380, call=0, batch=2, ticks=10),
        _span("lasana.stimulus", 115, 85, call=0, bytes=800),
        _span("lasana.prepare", 200, 60, call=0),
        _span("lasana.compile", 210, 40, kind="mono"),
        _span("lasana.execute", 260, 160, call=0),
        _span("lasana.fetch", 420, 65, call=0, bytes=160),
        _span("lasbench.record", 500, 20),
        _span("lasbench.call", 520, 470),
        _span("lasana.run", 530, 450, call=1, batch=2, ticks=10),
        _span("lasana.stimulus", 535, 65, call=1, bytes=800),
        _span("lasana.prepare", 600, 10, call=1),
        _span("lasana.execute", 610, 290, call=1),
        _span("lasana.fetch", 900, 75, call=1, bytes=160),
        _op("%copy.7", 150, 10),                     # the stimulus going in
        _op("%while.1", 270, 140),                   # a container
        _op("%fusion.1", 270, 60, "heads"),
        _op("%concatenate.2", 330, 30, "features"),
        _op("%fusion.3", 360, 20, "update"),         # [380, 400): loop only
        _op("%copy.4", 400, 10),
        _op("%fusion.1", 620, 180, "heads"),
        _op("%fusion.5", 800, 50, "drive"),
        _op("%fusion.6", 850, 40, "flush"),
        _op("%fusion.8", 1200, 50, "heads"),         # after the window
    ]


def test_reduce_by_hand():
    events = _made_up()
    r = program_trace.reduce(events, devices=1)
    assert {k: r[k] for k in ("window_s", "busy_s", "busy_max_s", "calls",
                              "device_planes")} == \
        {k: v for k, v in tracing.reduce([e[:5] for e in events], 1).items()
         if k != "breakdown"}
    assert r["busy_max_s"] == pytest.approx(420e-9)

    sp = r["spans"]
    assert set(sp) == {"lasana.run", "lasana.stimulus", "lasana.prepare",
                       "lasana.compile", "lasana.execute", "lasana.fetch"}

    def ns(name):
        return {k: (round(v * 1e9) if k.endswith("_s") else v)
                for k, v in sp[name].items()}
    assert ns("lasana.run") == {"count": 2, "total_s": 830, "self_s": 20,
                                "busy_s": 420, "bytes": 0}
    assert ns("lasana.stimulus") == {"count": 2, "total_s": 150,
                                     "self_s": 150, "busy_s": 10,
                                     "bytes": 1600}
    assert ns("lasana.prepare") == {"count": 2, "total_s": 70, "self_s": 30,
                                    "busy_s": 0, "bytes": 0}
    assert ns("lasana.compile")["count"] == 1
    assert ns("lasana.execute")["busy_s"] == 410
    assert ns("lasana.fetch") == {"count": 2, "total_s": 140, "self_s": 140,
                                  "busy_s": 0, "bytes": 320}

    stages = {k: round(v * 1e9) for k, v in r["stages"].items()}
    assert stages == {"heads": 240, "features": 30, "update": 20,
                      "drive": 50, "flush": 40, "other": 40}
    assert sum(stages.values()) == 420

    ops = dict(r["breakdown"]["device_ops"])
    assert {k: round(v * 1e9) for k, v in ops.items()} == {
        "heads:%fusion.1": 240, "drive:%fusion.5": 50, "flush:%fusion.6": 40,
        "features:%concatenate.2": 30, "update:%fusion.3": 20,
        "%copy.4": 10, "%copy.7": 10}
    gaps = [(lab, round(g * 1e9)) for lab, g in r["breakdown"]["idle_gaps"]]
    assert gaps == [("lasbench.record", 210),
                    ("host outside harness spans", 150),
                    ("lasana.compile", 110), ("lasana.fetch", 110)]


def test_readers_by_hand():
    ctx = {"trace": program_trace.reduce(_made_up(), devices=1),
           "counters": {"ticks": TICKS}}
    got = {n: read(ctx) for n, read in program_trace.READERS.items()}
    assert got == pytest.approx({
        "stimulus_ms_per_call": (150 - 10) / 2 * 1e-6,
        "fetch_ms_per_call": 140 / 2 * 1e-6,
        "stimulus_gb_per_s": 1600 / 150,
        "heads_us_per_tick": 240e-3 / TICKS,
        "features_us_per_tick": 30e-3 / TICKS})


HLO = """HloModule jit_sim, is_scheduled=true, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(sim)/while/body/closed_call/heads/add"}
}

ENTRY %main.5 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0:T(128)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(sim)/while/body/closed_call/heads/pnf,pfh->pnh/dot_general" stack_frame_id=3}
  %copy-start.2 = (f32[4]{0:T(128)}, f32[4]{0:T(128)S(1)}, u32[]{:S(2)}) copy-start(%fusion.1)
  %copy-done.2 = f32[4]{0:T(128)S(1)} copy-done(%copy-start.2)
  %concatenate.3 = f32[8]{0} concatenate(%copy-done.2, %Arg_0.1), dimensions={0}, metadata={op_name="jit(sim)/while/body/closed_call/features/concatenate"}
  %dynamic-update-slice.4 = f32[8]{0} dynamic-update-slice(%concatenate.3, %Arg_0.1, %c), metadata={op_name="jit(sim)/while/body/dynamic_update_slice"}
  ROOT %copy.5 = f32[4]{0} copy(%Arg_0.1)
}
"""


def test_op_stages_from_hlo():
    """Stages by ``op_name``; a copy the compiler added takes its
    operand's; scan mechanics and parameters have none."""
    assert program_trace.op_stages([HLO]) == {
        ("jit_sim", "add.1"): "heads", ("jit_sim", "fusion.1"): "heads",
        ("jit_sim", "copy-start.2"): "heads",
        ("jit_sim", "copy-done.2"): "heads",
        ("jit_sim", "concatenate.3"): "features"}
    assert program_trace._op_key(
        "jit_sim(8329475)", "%copy-done.2 = f32[4]{0} copy-done(...)") == \
        ("jit_sim", "copy-done.2")


@pytest.mark.parametrize("cell,named", [
    ("snn", {"fusion.232": "heads", "fusion.233": "heads",
             "concatenate.336": "features"}),
    ("xbar", {"concatenate.245": "features", "fusion.233": "heads",
              "reshape.364": "drive", "copy-done.15": "drive"})])
def test_op_stages_on_chip_hlo(cell, named):
    """The mono programs as the TPU compiler left them (``compiled_hlo()``
    on a v5e): every dot (a ``convolution`` there) and concatenate has a
    stage, and the operations that lead the cells' breakdowns have the
    stage they compute."""
    with gzip.open(os.path.join(HARNESS, "data",
                                f"hlo_v5e_{cell}_batch.txt.gz"), "rt") as f:
        text = f.read()
    stages = program_trace.op_stages([text])
    ops = [m.group(1) for m in map(program_trace.HLO_OP.match,
                                   text.splitlines())
           if m and re.search(r"= \S+ (dot|convolution|concatenate)\(",
                                m.group(0))]
    assert len(ops) > 10
    assert all(("jit_sim", op) in stages for op in ops)
    assert {op: stages[("jit_sim", op)] for op in named} == named


def _old_trace():
    with gzip.open(os.path.join(HARNESS, "data",
                                "trace_v5e_snn_batch.json.gz"), "rt") as f:
        return json.load(f)


def test_readers_find_nothing_without_the_engine_spans():
    """A program without spans or scopes, and a summary without them,
    read None: what the readers give on a parent commit."""
    events = _old_trace()
    r = program_trace.reduce(events, devices=1)
    assert r["spans"] == {}
    assert r["stages"] == {"other": pytest.approx(r["busy_max_s"])}
    plain = tracing.reduce(events, devices=1)
    for trace in (r, plain):
        ctx = {"trace": trace, "counters": {"ticks": 100}}
        assert {n: read(ctx) for n, read in
                program_trace.READERS.items()} == dict.fromkeys(
                    program_trace.READERS)


def _recorded():
    with gzip.open(os.path.join(HARNESS, "data",
                                "trace_v5e_snn_batch_spans.json.gz"),
                   "rt") as f:
        return json.load(f)


def test_recorded_spans_against_a_timeline():
    events = _recorded()
    r = program_trace.reduce(events, devices=1)
    shared = tracing.reduce([e[:5] for e in events], devices=1)
    for k in ("window_s", "busy_s", "busy_max_s", "calls"):
        assert r[k] == shared[k]
    (w0, w1), = [(s, s + d) for _, _, n, s, d, _ in events
                 if n == "lasbench.window"]
    mark = np.zeros(w1 - w0, bool)
    by_stage = {}
    for plane, line, name, s, d, st in events:
        if plane == DEV and line == OPS:
            a, b = max(s, w0) - w0, min(s + d, w1) - w0
            if b > a:
                mark[a:b] = True
                if not name.startswith(tracing.CONTAINERS):
                    m = by_stage.setdefault(st.get("stage", "other"),
                                            np.zeros(w1 - w0, bool))
                    m[a:b] = True
    assert r["busy_max_s"] == pytest.approx(mark.sum() * 1e-9)
    named = np.zeros(w1 - w0, bool)
    for stage in program_trace.STAGES:
        if stage in by_stage:
            named |= by_stage[stage]
            assert r["stages"][stage] == pytest.approx(
                by_stage[stage].sum() * 1e-9)
    assert r["stages"]["other"] == pytest.approx(
        (mark & ~named).sum() * 1e-9)
    assert r["stages"]["heads"] > 0.5 * r["busy_max_s"]

    runs = [(s, s + d, st) for _, _, n, s, d, st in events
            if n == "lasana.run"]
    assert r["spans"]["lasana.run"]["count"] == len(runs) >= 2
    for name in ("lasana.stimulus", "lasana.fetch", "lasana.execute"):
        spans = [(s - w0, s + d - w0, st) for _, _, n, s, d, st in events
                 if n == name]
        sp = r["spans"][name]
        assert sp["count"] == len(spans) == len(runs)
        assert sp["busy_s"] == pytest.approx(
            sum(mark[a:b].sum() for a, b, _ in spans) * 1e-9)
        assert sp["bytes"] == sum(st.get("bytes", 0) for *_, st in spans)
    stim = r["spans"]["lasana.stimulus"]
    assert stim["bytes"] == len(runs) * 1024 * 100 * 784 * 4
