"""Device time outside the Pallas kernel per tick, in ms: the delta test,
the splice, the hysteresis ``while_loop`` (``core/nms.py``), the state
concatenation and split."""


def read(ctx):
    trace, window = ctx["trace"], ctx["window"]
    steps = ctx["record"].get("steps")
    if trace is None or window is None or not steps:
        return None
    busy = trace.mean_busy_s(window, kernel=False)
    return busy / steps * 1e3 if busy is not None else None
