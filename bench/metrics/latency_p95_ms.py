"""95th percentile of frame latency over every frame of the window, from
its due time on the wall clock to the return of the step that served it
(a frame not served at the first try counts to the end of the window), in
ms. In the stream cells this tail is set by stalls of the host process and
the backlog each leaves behind, so it is read beside the end-to-end median
rather than bounded."""
import numpy as np


def read(ctx):
    xs = ctx["record"].get("latency_s")
    return float(np.percentile(xs, 95)) * 1e3 if xs else None
