"""The tick operation count against a count made by hand."""

import numpy as np
import pytest

from lasbench.flops import head_flops, tick_flops


def _linear(f):
    return {"family": "linear", "arrays": {"w": np.zeros(f + 1),
                                           "mu": np.zeros(f),
                                           "sd": np.ones(f)}}


def _mlp(f, widths=(100, 50)):
    dims = (f,) + widths + (1,)
    arrays = {f"w{i}": np.zeros((a, b)) for i, (a, b) in
              enumerate(zip(dims[:-1], dims[1:]))}
    return {"family": "mlp", "arrays": arrays}


def test_head_flops_by_family():
    assert head_flops(_linear(10)) == 20
    assert head_flops(_mlp(12)) == 2 * (12 * 100 + 100 * 50 + 50 * 1)
    assert head_flops({"family": "mean", "arrays": {"mu": np.zeros(())}}) == 0
    with pytest.raises(ValueError):
        head_flops({"family": "gbdt", "arrays": {}})


def test_tick_flops_hand_count_lif_and_crossbar():
    # lif heads: active rows have 10 columns, transition rows 12
    lif = {"heads": {"M_ES": _mlp(10), "M_V": _linear(10), "M_O": _mlp(10),
                     "M_ED": _linear(12), "M_L": _mlp(12, (8,))}}
    xb = {"heads": {p: _linear(68) for p in
                    ("M_ES", "M_V", "M_O", "M_ED", "M_L")}}
    layers = [{"kind": "lif", "weight": np.zeros((4, 3))},
              {"kind": "crossbar", "weight": np.zeros((40, 5))}]
    b = 2
    mlp10 = 2 * (10 * 100 + 100 * 50 + 50)
    # per lif circuit: idle M_ES + M_V, active M_O + M_V + M_ES,
    # transition M_ED + M_L
    per_lif = (mlp10 + 20) + (mlp10 + 20 + mlp10) + (24 + 2 * (12 * 8 + 8))
    lif_ops = 2 * b * 4 * 3 + b * 3 * per_lif
    # crossbar: 40 inputs make 2 segments of 32, so 2 rows per output
    xb_ops = 2 * b * 40 * 5 + b * 5 * 2 * 7 * (2 * 68)
    assert tick_flops(layers, b, {"lif": lif, "crossbar": xb}) == \
        lif_ops + xb_ops
