"""Device time of the Pallas megakernel per request, in ms: the summed
kernel events in the window (``bench/trace.py`` finds them by their
``custom-call`` opcode, whatever the kernel is named) over the requests
served."""


def read(ctx):
    trace, window = ctx["trace"], ctx["window"]
    n = ctx["record"].get("requests")
    if trace is None or window is None or not n:
        return None
    kernel_s = trace.mean_busy_s(window, kernel=True)
    return kernel_s / n * 1e3 if kernel_s else None
