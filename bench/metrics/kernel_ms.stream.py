"""Device time of the masked stream kernel per tick, in ms: the summed
Pallas kernel events in the window over the engine steps it ran."""


def read(ctx):
    trace, window = ctx["trace"], ctx["window"]
    steps = ctx["record"].get("steps")
    if trace is None or window is None or not steps:
        return None
    kernel_s = trace.mean_busy_s(window, kernel=True)
    return kernel_s / steps * 1e3 if kernel_s else None
