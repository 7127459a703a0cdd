"""Share of the engine's steps (the harness's ``bench.step`` spans) in
which no operation ran on the first chip, in %. The whole window's idle
share would mostly measure the offered rate."""
from bench.trace import clip, overlap, total


def read(ctx):
    trace, window = ctx["trace"], ctx["window"]
    if trace is None or window is None or not trace.chips():
        return None
    steps = clip(trace.span_intervals("bench.step"), window)
    span = total(steps)
    if not span:
        return None
    busy = trace.busy(trace.chips()[0], window)
    return 100.0 * (1.0 - overlap(busy, steps) / span)
