"""The least work an edge call needs, whatever implements it.

Bytes: every input frame read once, every output plane written once, and
one f32 peak per frame. Re-read halos, block shape and the per-block maxima
an implementation keeps are its own choice and not counted.

Operations: the four dense 5x5 correlations of the paper's operator (one
multiply and one add per non-zero tap), then the magnitude (four squares,
three adds, one square root).
"""
from __future__ import annotations

import numpy as np

PEAK_BYTES = 4
MAGNITUDE_OPS = 8


def frame_bytes(frames: int, h: int, w: int, in_dtype: str, out_planes=("float32",)
                ) -> int:
    px = frames * h * w
    out = sum(np.dtype(d).itemsize for d in out_planes)
    return px * (np.dtype(in_dtype).itemsize + out) + frames * PEAK_BYTES


def edge_ops(frames: int, h: int, w: int, bank) -> int:
    taps = sum(int(np.count_nonzero(k)) for k in bank)
    return frames * h * w * (2 * taps + MAGNITUDE_OPS)


def roofline_seconds(bytes_: int, ops: int, peaks: dict) -> tuple:
    """(least seconds, bound): the larger of the byte and operation bounds."""
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["bf16_flops_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_ops else (t_ops, "flops")
