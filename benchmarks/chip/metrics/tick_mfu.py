"""The whole tick's share of the chips' bf16 peak: Algorithm 1's dense
operations per tick at the cell's shapes (``lasbench.flops``), times the
ticks the traced window simulated, over the window's length, the chips
and the peak of ``peaks.json``, in percent."""

from lasbench.flops import tick_flops


def read(ctx):
    peaks, trace = ctx["peaks"], ctx["trace"]
    ticks = ctx["counters"].get("ticks")
    if peaks is None or not ticks or not trace["window_s"]:
        return None
    net = ctx["net"]
    ops = tick_flops(net.layers, ctx["cell"].traffic["batch"],
                     net.artifacts) * ticks
    return 100.0 * ops / (trace["window_s"] * ctx["chips"]
                          * peaks["bf16_flops_per_s"])
