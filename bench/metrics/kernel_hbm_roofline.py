"""The megakernel's share of its roofline, in %: the least time the chip
could take for the window's requests (``bench/traffic_bytes.py``: bytes
over HBM bandwidth, or operations over peak, the larger) over the summed
device time of the Pallas kernel's events in the window."""
from bench.traffic_bytes import roofline_seconds


def read(ctx):
    trace, window, peaks = ctx["trace"], ctx["window"], ctx["peaks"]
    rec = ctx["record"]
    if trace is None or window is None or not peaks or not rec.get("requests"):
        return None
    kernel_s = trace.mean_busy_s(window, kernel=True)
    if not kernel_s:
        return None
    least, bound = roofline_seconds(rec["min_bytes"], rec["min_ops"], peaks)
    print(f"kernel_hbm_roofline: {rec['min_bytes']} B, {rec['min_ops']} ops "
          f"({rec['min_ops'] / rec['min_bytes']:.3f} ops/B), bound by "
          f"{bound}: least {least:.6f} s against {kernel_s:.6f} s of kernel")
    return 100.0 * least / kernel_s
