"""Algorithm 1's dense operations per network tick, from shapes alone.

Per layer: the drive matmul ``2 * B * fan_in * n_out`` (for a crossbar
layer, the rows' multiply-accumulate) and that of each incoming edge,
``2 * B * n_src * n_dst``, plus, for every circuit, the seven head
evaluations of one tick (idle M_ES, M_V; active M_O, M_V, M_ES;
transition M_ED, M_L), each counted as the multiply-adds of the head's
family at the widths read from the surrogate artifact's arrays. What
implements them does not change the count.
"""

from __future__ import annotations

TICK_HEADS = ("M_ES", "M_V", "M_O", "M_V", "M_ES", "M_ED", "M_L")
XB_INPUTS = 32


def head_flops(head: dict) -> int:
    """Multiply-add operations of one evaluation of one predictor."""
    a = head["arrays"]
    if head["family"] == "mean":
        return 0
    if head["family"] == "linear":
        return 2 * (a["w"].shape[0] - 1)
    if head["family"] == "mlp":
        return sum(2 * a[k].shape[0] * a[k].shape[1]
                   for k in a if k.startswith("w"))
    raise ValueError(f"no operation count for family {head['family']!r}")


def tick_flops(layers: list, batch: int, artifacts: dict) -> float:
    """``layers`` as the reference reads them; ``artifacts`` by kind."""
    total = 0
    for layer in layers:
        fan_in, n_out = layer["weight"].shape
        total += 2 * batch * fan_in * n_out
        for edge in layer.get("edges_in", ()):
            n_src, n_dst = edge["weight"].shape
            total += 2 * batch * n_src * n_dst
        n = batch * n_out
        if layer["kind"] == "crossbar":
            n *= -(-fan_in // XB_INPUTS)
        heads = artifacts[layer["kind"]]["heads"]
        total += n * sum(head_flops(heads[h]) for h in TICK_HEADS)
    return float(total)
