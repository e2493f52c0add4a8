"""The comparison's numbers on records made by hand."""

import types

import numpy as np

from lasbench import check

T, L, B = 4, 2, 3


def _ref(latency):
    return {"published": [np.zeros((T, B, 5)), np.zeros((T, B, 2))],
            "energy": np.ones((T, L, B)), "latency": latency,
            "events": np.ones((T, L, B), np.int64),
            "flush": np.ones((L, B))}


def _run(ref):
    """The reference's records in the program's place."""
    return check.as_record(ref, types.SimpleNamespace(
        circuits=("lif", "lif"), layer_spikes=[]))


def test_latency_gap_over_a_reference_without_latency_is_exact():
    """A seed whose drawn M_L head reads below zero on every row that
    spikes has no latency on any tick; a program that reads none either
    agrees, one that reads any does not."""
    ref = _ref(np.zeros((T, L, B)))
    same = _run(ref)
    assert check.compare([(same, None)], [ref])["latency_gap_pct"] == 0.0
    off = _run(ref)
    off.latency = off.latency + 1e-3
    assert check.compare([(off, None)], [ref])["latency_gap_pct"] == \
        float("inf")


def test_latency_gap_is_a_share_of_the_reference():
    ref = _ref(np.full((T, L, B), 2.0))
    run = _run(ref)
    run.latency = run.latency * 1.01
    got = check.compare([(run, None)], [ref])["latency_gap_pct"]
    np.testing.assert_allclose(got, 1.0)
