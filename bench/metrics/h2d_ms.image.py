"""Host->device copy of one request: the harness's span around
``jax.device_put`` and ``block_until_ready``, median over the window's
requests, in ms."""
import numpy as np


def read(ctx):
    xs = ctx["record"].get("h2d_s")
    return float(np.median(xs)) * 1e3 if xs else None
