"""Share of the traced window in which no operation ran on the device
(mean over the chips used), in percent, in the closed-loop batch cells."""


def read(ctx):
    trace = ctx["trace"]
    if not trace["device_planes"] or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
