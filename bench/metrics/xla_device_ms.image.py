"""Device time outside the Pallas kernel per request, in ms: the peak and
normalisation XLA runs after the kernel (``dispatch.edge``), and any other
device operation in the window."""


def read(ctx):
    trace, window = ctx["trace"], ctx["window"]
    n = ctx["record"].get("requests")
    if trace is None or window is None or not n:
        return None
    busy = trace.mean_busy_s(window, kernel=False)
    return busy / n * 1e3 if busy is not None else None
