"""Device-busy time of the traced window on the busiest chip, divided by
the network ticks the window simulated, in microseconds."""


def read(ctx):
    ticks = ctx["counters"].get("ticks")
    busy = ctx["trace"]["busy_max_s"]
    if not ticks or not busy:
        return None
    return busy / ticks * 1e6
