"""The numbers that decide ``correct``: program outputs against the plain
reference, on the same frames.

  mag_err     the largest |served magnitude - reference| over every pixel
              of every compared frame, as a share of the full scale 255;
  edge_diff   the most edge pixels that differ in one compared frame;
  missing     compared frames that were due and never served.

Each is held against the limit in ``bench/limits/<workload>.json``. A
number that is not finite (a NaN or an infinity in the served output) is
the worst there is: it is kept as infinity and meets no limit.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional


class Checks:
    """Running maxima of the compared numbers over the compared frames."""

    def __init__(self):
        self.values: Dict[str, float] = {}
        self.frames = 0

    def _worse(self, name: str, value: float) -> None:
        value = float(value)
        if not math.isfinite(value):
            value = math.inf
        self.values[name] = max(self.values.get(name, 0.0), value)

    def frames_pair(self, got: dict, want: dict) -> None:
        """Compare one batch of served outputs with the reference's."""
        import jax.numpy as jnp

        mag = jnp.max(jnp.abs(got["magnitude"].astype(jnp.float32)
                              - want["magnitude"])) / 255.0
        self._worse("mag_err", float(mag))
        if "edges" in want:
            diff = jnp.sum(got["edges"] != want["edges"], axis=(-2, -1))
            self._worse("edge_diff", float(jnp.max(diff)))
        self.frames += int(want["magnitude"].shape[0])

    def missing(self, count: int) -> None:
        self._worse("missing", count)

    def verdict(self, limits: dict) -> tuple:
        """(correct, [(name, value, limit)]). A number with no limit, or a
        run that compared nothing, is not correct."""
        rows = [(name, value, limits.get(name))
                for name, value in sorted(self.values.items())]
        ok = self.frames > 0 and all(
            limit is not None and value <= limit for _, value, limit in rows
        )
        for name in limits:
            if name not in self.values and name != "missing":
                rows.append((name, None, limits[name]))
                ok = False
        return ok, rows


def _plain(value):
    """A compared number as JSON can hold it: infinity as a string."""
    return "inf" if value == math.inf else value


def report(rows: Iterable[tuple]) -> Dict[str, dict]:
    return {name: {"value": _plain(value), "limit": limit}
            for name, value, limit in rows}


def format_rows(rows: Iterable[tuple], frames: Optional[int] = None) -> list:
    lines = [f"check {name}: {value!r} (limit {limit!r})"
             for name, value, limit in rows]
    if frames is not None:
        lines.insert(0, f"compared {frames} frame(s) with the reference")
    return lines
