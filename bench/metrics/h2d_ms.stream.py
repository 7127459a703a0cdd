"""Host->device copy of one tick's batch of frames, as the stream engine
times it (``StreamStats.transfer_ms``), median over the window's ticks."""
import numpy as np


def read(ctx):
    xs = ctx["record"].get("transfer_ms")
    return float(np.median(xs)) if xs else None
