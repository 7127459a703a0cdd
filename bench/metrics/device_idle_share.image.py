"""Share of the traced window in which no operation ran on the device,
averaged over the chips used, in %."""


def read(ctx):
    trace, window = ctx["trace"], ctx["window"]
    if trace is None or window is None or window[1] <= window[0]:
        return None
    busy = trace.mean_busy_s(window)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ((window[1] - window[0]) / 1e9))
