"""The reduction from trace events to busy time, idle share, per-call host
time and the breakdown: by hand on a made-up trace, and against a plain
timeline count on a trace recorded on a TPU v5e."""

import gzip
import json
import os

import numpy as np
import pytest

from lasbench import tracing

HARNESS = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"
OPS = "XLA Ops"


def _ev(name, start, dur, plane=DEV, line=OPS):
    return [plane, line, name, start, dur]


def test_reduce_by_hand():
    host = "/host:CPU"
    events = [
        _ev("lasbench.window", 0, 1000, host, "python"),
        _ev("lasbench.call", 100, 400, host, "python"),
        _ev("lasbench.record", 500, 100, host, "python"),
        _ev("lasbench.call", 600, 350, host, "python"),
        _ev("fusion.1", 150, 100),          # [150, 250)
        _ev("fusion.2", 200, 100),          # overlaps: union [150, 300)
        _ev("dot.3", 700, 150),             # [700, 850)
        _ev("dot.3", 990, 50),              # clipped to [990, 1000)
        _ev("fusion.1", 1200, 50),          # after the window
        _ev("other", 0, 1000, "/device:TPU:0 SparseCore", OPS),
        _ev("module", 0, 1000, DEV, "XLA Modules"),
    ]
    r = tracing.reduce(events, devices=1)
    assert r["window_s"] == pytest.approx(1000e-9)
    busy = 150 + 150 + 10
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert r["busy_max_s"] == pytest.approx(busy * 1e-9)
    assert r["device_planes"] == [DEV]
    assert r["calls"] == [pytest.approx((400e-9, 150e-9)),
                          pytest.approx((350e-9, 150e-9))]
    ops = dict((n, t) for n, t in r["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.1": 100e-9, "fusion.2": 100e-9,
                                 "dot.3": 160e-9})
    gaps = r["breakdown"]["idle_gaps"]
    # gaps: [0,150) outside calls, [300,700) mostly in the first call's
    # end, the record span and the second call, [850,990) in call 2
    assert [round(g * 1e9) for _, g in gaps] == [400, 150, 140]
    assert gaps[0][0] == "lasbench.record"
    assert gaps[1][0] == "host outside harness spans"
    assert gaps[2][0] == "lasbench.call"


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        tracing.reduce([_ev("fusion.1", 0, 10)], devices=1)


def _timeline_busy(events, w0, w1):
    """Busy nanoseconds by marking a boolean timeline, one slot per ns."""
    mark = np.zeros(w1 - w0, bool)
    for plane, line, _, s, d in events:
        if plane == DEV and line == OPS:
            a, b = max(s, w0) - w0, min(s + d, w1) - w0
            if b > a:
                mark[a:b] = True
    return int(mark.sum()), mark


def test_recorded_trace_against_a_timeline():
    with gzip.open(os.path.join(HARNESS, "data",
                           "trace_v5e_snn_batch.json.gz"), "rt") as f:
        events = json.load(f)
    r = tracing.reduce(events, devices=1)
    (w0, w1), = [(s, s + d) for _, _, n, s, d in events
                 if n == "lasbench.window"]
    busy, mark = _timeline_busy(events, w0, w1)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    calls = [(s, s + d) for _, _, n, s, d in events if n == "lasbench.call"]
    assert len(r["calls"]) == len(calls) > 0
    for (span, inside), (s, e) in zip(r["calls"], calls):
        assert span == pytest.approx((e - s) * 1e-9)
        assert inside == pytest.approx(mark[s - w0:e - w0].sum() * 1e-9)
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert len(r["breakdown"]["idle_gaps"]) <= 10
    idle = sorted(int(g * 1e9) for _, g in r["breakdown"]["idle_gaps"])
    assert sum(idle) <= (w1 - w0) - busy
