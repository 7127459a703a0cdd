"""The profiler's trace, reduced to intervals the metric readers share.

A traced run writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it. On a TPU each chip is a plane ``/device:TPU:<n>``, whose ``XLA Ops``
line holds one event per device operation. The harness's own spans
(``jax.profiler.TraceAnnotation("bench.<name>")``) are events of a host
plane. Both are on the profiler's clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# A TPU op event is named by its HLO instruction,
# ``%edge_pallas.1 = (f32[...], ...) custom-call(...)``: a Pallas kernel is
# an instruction whose opcode is ``custom-call``.
KERNEL_OPCODE = " custom-call("


class Op:
    __slots__ = ("name", "start", "end", "kernel")

    def __init__(self, name: str, start: int, end: int, kernel: bool):
        self.name, self.start, self.end, self.kernel = name, start, end, kernel


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def overlap(a: List[Interval], b: List[Interval]) -> int:
    """Length of the intersection of two disjoint sorted covers."""
    i = j = 0
    got = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            got += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return got


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(cover: List[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that ``cover`` leaves out."""
    out, cur = [], window[0]
    for s, e in cover:
        if s > cur:
            out.append((cur, min(s, window[1])))
        cur = max(cur, e)
    if cur < window[1]:
        out.append((cur, window[1]))
    return [(s, e) for s, e in out if e > s]


def _is_kernel(name: str, stats: Dict[str, object]) -> bool:
    return KERNEL_OPCODE in name or stats.get("hlo_category") == "custom-call"


def short_name(name: str) -> str:
    """``%edge_pallas.1`` of ``%edge_pallas.1 = (...) custom-call(...)``."""
    return name.split(" = ", 1)[0]


class Trace:
    """Device operations per chip, and the harness's spans, of one trace."""

    def __init__(self, ops: Dict[str, List[Op]], spans: List[Tuple[str, int, int]]):
        self.ops = ops
        self.spans = spans

    @classmethod
    def from_profile(cls, data) -> "Trace":
        ops: Dict[str, List[Op]] = {}
        spans: List[Tuple[str, int, int]] = []
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PREFIX):
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    chip = ops.setdefault(plane.name, [])
                    for ev in line.events:
                        start = int(ev.start_ns)
                        end = start + int(ev.duration_ns)
                        chip.append(Op(ev.name, start, end,
                                       _is_kernel(ev.name, dict(ev.stats))))
            elif not plane.name.startswith("/device:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            start = int(ev.start_ns)
                            spans.append((ev.name, start,
                                          start + int(ev.duration_ns)))
        return cls(ops, spans)

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        from jax.profiler import ProfileData

        paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_profile(ProfileData.from_file(paths[-1]))

    def span_intervals(self, name: str) -> List[Interval]:
        return union((s, e) for n, s, e in self.spans if n == name)

    def window(self) -> Optional[Interval]:
        w = self.span_intervals("bench.window")
        return (w[0][0], w[-1][1]) if w else None

    def chips(self) -> List[str]:
        return sorted(self.ops)

    def busy(self, chip: str, window: Interval, *, kernel: Optional[bool] = None
             ) -> List[Interval]:
        """Union of op intervals on ``chip`` within ``window``; ``kernel``
        keeps only Pallas kernels (True) or only the rest (False)."""
        ops = self.ops.get(chip, [])
        sel = (o for o in ops if kernel is None or o.kernel == kernel)
        return clip(union((o.start, o.end) for o in sel), window)

    def mean_busy_s(self, window: Interval, *, kernel: Optional[bool] = None
                    ) -> Optional[float]:
        """Busy seconds in ``window`` averaged over the chips traced."""
        chips = self.chips()
        if not chips:
            return None
        return sum(total(self.busy(c, window, kernel=kernel))
                   for c in chips) / len(chips) / 1e9

    def top_ops(self, window: Interval, n: int = 10) -> List[list]:
        """[[name, seconds], ...]: the device operations that took most time,
        summed by instruction name over every chip. A ``while`` and the
        operations of its body both count."""
        acc: Dict[str, int] = {}
        for chip in self.chips():
            for o in self.ops[chip]:
                s, e = max(o.start, window[0]), min(o.end, window[1])
                if e > s:
                    key = short_name(o.name)
                    acc[key] = acc.get(key, 0) + e - s
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, window: Interval, n: int = 10) -> List[list]:
        """[[span, seconds], ...]: the longest idle gaps of the first chip,
        each named by the harness span it falls in most."""
        chips = self.chips()
        if not chips:
            return []
        idle = gaps(self.busy(chips[0], window), window)
        named = sorted({n for n, _, _ in self.spans} - {"bench.window"})
        covers = {name: self.span_intervals(name) for name in named}
        out = []
        for g in sorted(idle, key=lambda iv: iv[0] - iv[1])[:n]:
            best, best_ns = "outside bench spans", 0
            for name, cover in covers.items():
                ns = overlap([g], cover)
                if ns > best_ns:
                    best, best_ns = name, ns
            out.append([best, (g[1] - g[0]) / 1e9])
        return out
