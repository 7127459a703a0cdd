#!/usr/bin/env python3
"""The program's own spans in a profiler trace: where a stream step's host
time goes.

    python3 bench/program_trace.py --workload cam1080-noisy --seed <n> --seconds 51

The stream engine marks each step ``repro.stream.step`` (metadata ``step``,
``frames``, ``groups``) and its phases ``repro.stream.<phase>``; the guard
marks ``repro.guard.attempt`` and ``repro.guard.backoff``. JAX marks each
jit cache miss with ``lower_sharding_computation`` and
``backend_compile_and_load``. :class:`ProgramSpans` keeps these host
events of a trace. :class:`bench.trace.Trace`, which the per-layer metrics
read, keeps only the device's operations and the harness's ``bench.*``
spans.

As a script it sets up the cell as ``bench/run.py`` does, measures the
window under the profiler with the same options, and prints: each phase's
median time a step, the device's idle time put down to the innermost span
over it, the phase that held most of each of the five longest steps, the
share of idle time inside steps that no phase covers, and the programs
compiled inside the window. The last line is the same numbers as one JSON
object. It compares no outputs and is no benchmark result.
"""
from __future__ import annotations

import argparse
import bisect
import gc
import glob
import heapq
import json
import os
import sys
import tempfile
from statistics import median
from typing import Dict, Iterable, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.trace import Interval, gaps, overlap, union  # noqa: E402

PROGRAM_PREFIX = "repro."
STEP_SPAN = "repro.stream.step"
PHASES = ("intake", "stack", "h2d", "concat", "delta", "compute", "split",
          "account", "police")
# JAX's own events on a jit cache miss: tracing and lowering, then the
# backend's compile (or the persistent cache's load).
LOWER_EVENT = "lower_sharding_computation"
COMPILE_EVENTS = (LOWER_EVENT, "backend_compile_and_load")
# A jitted call's host event, ``PjitFunction(<function name>)``: the
# innermost one around a compile event names what compiled.
JIT_CALL = "PjitFunction("

Event = Tuple[str, int, int, dict]      # name, start ns, end ns, metadata
Piece = Tuple[int, int, str]            # start ns, end ns, span name


def innermost(spans: Iterable[Tuple[str, int, int]]) -> List[Piece]:
    """Disjoint sorted pieces of the time ``spans`` cover, each put down to
    the innermost span over it (the latest to start, the shortest on a
    tie): a span's self time is its pieces."""
    spans = sorted((s, e, n) for n, s, e in spans if e > s)
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out: List[Piece] = []
    live: list = []
    i = 0
    for lo, hi in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i][0] <= lo:
            s, e, n = spans[i]
            heapq.heappush(live, (-s, e - s, e, n))
            i += 1
        # A span that ended under a later one leaves the heap when it
        # comes to the top.
        while live and live[0][2] <= lo:
            heapq.heappop(live)
        if live:
            out.append((lo, hi, live[0][3]))
    return out


def overlap_each(pieces: List[Piece], cover: List[Interval]) -> List[int]:
    """For each of the disjoint sorted ``pieces``, its overlap with the
    disjoint sorted ``cover``."""
    out, j = [], 0
    for lo, hi, _ in pieces:
        while j < len(cover) and cover[j][1] <= lo:
            j += 1
        got, k = 0, j
        while k < len(cover) and cover[k][0] < hi:
            got += min(hi, cover[k][1]) - max(lo, cover[k][0])
            k += 1
        out.append(got)
    return out


def _jit_name(start: int, end: int, calls: List[Piece]) -> str:
    """The function of the innermost jitted call around [start, end)."""
    around = [c for c in calls if c[0] <= start and end <= c[1]]
    if not around:
        return "?"
    name = max(around, key=lambda c: (c[0], -c[1]))[2]
    return name[len(JIT_CALL):].rstrip(")")


class ProgramSpans:
    """The ``repro.*`` spans with their metadata, and JAX's compile events
    with the ``function`` that compiled, of one trace."""

    def __init__(self, events: List[Event]):
        self.events = events

    @classmethod
    def from_profile(cls, data) -> "ProgramSpans":
        events: List[Event] = []
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                calls: List[Piece] = []
                compiles: List[Tuple[str, int, int]] = []
                for ev in line.events:
                    name, start = ev.name, int(ev.start_ns)
                    end = start + int(ev.duration_ns)
                    if name.startswith(PROGRAM_PREFIX):
                        events.append((name, start, end, dict(ev.stats)))
                    elif name in COMPILE_EVENTS:
                        compiles.append((name, start, end))
                    elif name.startswith(JIT_CALL):
                        calls.append((start, end, name))
                for name, start, end in compiles:
                    events.append((name, start, end, {
                        "function": _jit_name(start, end, calls)}))
        return cls(events)

    @classmethod
    def from_dir(cls, log_dir: str) -> "ProgramSpans":
        from jax.profiler import ProfileData

        paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_profile(ProfileData.from_file(paths[-1]))

    def intervals(self, name: str, window: Interval
                  ) -> List[Tuple[int, int, dict]]:
        """``(start, end, metadata)`` of the ``name`` events that start
        inside ``window``, in order."""
        return sorted(((s, e, m) for n, s, e, m in self.events
                       if n == name and window[0] <= s < window[1]),
                      key=lambda ev: ev[:2])

    def phase_per_step(self, phase: str, window: Interval) -> List[int]:
        """ns of ``phase`` spans inside each ``repro.stream.step`` that
        starts in ``window``, one number a step (0 where it did not run)."""
        steps = self.intervals(STEP_SPAN, window)
        starts = [s for s, _, _ in steps]
        per = [0] * len(steps)
        for n, s, e, _ in self.events:
            if n != phase:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= steps[i][1]:
                per[i] += e - s
        return per

    def pieces(self, window: Interval) -> List[Piece]:
        """:func:`innermost` of the ``repro.*`` spans, clipped to
        ``window``."""
        return [(max(lo, window[0]), min(hi, window[1]), n)
                for lo, hi, n in innermost(
                    (n, s, e) for n, s, e, _ in self.events
                    if n.startswith(PROGRAM_PREFIX))
                if min(hi, window[1]) > max(lo, window[0])]

    def idle_by_span(self, idle: List[Interval], window: Interval
                     ) -> Dict[str, int]:
        """ns of the device's ``idle`` intervals put down to the innermost
        ``repro.*`` span over them; idle time no such span covers is left
        out."""
        pieces = self.pieces(window)
        out: Dict[str, int] = {}
        for (_, _, name), ns in zip(pieces, overlap_each(pieces, idle)):
            if ns:
                out[name] = out.get(name, 0) + ns
        return out


def report(trace, spans: ProgramSpans) -> Optional[dict]:
    """Where the steps in ``trace``'s window went; prints each finding and
    returns them, or None where the trace has no window. ``trace`` is the
    same trace as a :class:`bench.trace.Trace`, for the window and the
    first chip's idle time."""
    window = trace.window()
    if window is None:
        return None
    out: dict = {}
    steps = spans.intervals(STEP_SPAN, window)
    out["steps"] = len(steps)
    if steps:
        out["step_ms"] = median([(e - s) / 1e6 for s, e, _ in steps])
        out["phase_ms"] = {
            p: median([ns / 1e6 for ns in spans.phase_per_step(
                f"repro.stream.{p}", window)]) for p in PHASES}
        print(f"{len(steps)} steps, median {out['step_ms']:.4f} ms; "
              "median ms a step: " + ", ".join(
                  f"{p} {ms:.4f}" for p, ms in out["phase_ms"].items()))
    chips = trace.chips()
    if steps and chips:
        idle = gaps(trace.busy(chips[0], window), window)
        in_steps = overlap(idle, union((s, e) for s, e, _ in steps))
        by_span = spans.idle_by_span(idle, window)
        out["idle_s_by_span"] = {n: ns / 1e9 for n, ns in sorted(
            by_span.items(), key=lambda kv: -kv[1])}
        for name, secs in out["idle_s_by_span"].items():
            print(f"device idle {secs:.6f} s under {name}")
        if in_steps:
            out["unattributed_idle_pct"] = (
                100.0 * by_span.get(STEP_SPAN, 0) / in_steps)
            print("idle in steps under no phase: "
                  f"{out['unattributed_idle_pct']:.3f}%")
        pieces = spans.pieces(window)
        for s, e, meta in sorted(steps, key=lambda st: st[0] - st[1])[:5]:
            held: Dict[str, int] = {}
            for lo, hi, name in pieces:
                if s <= lo and hi <= e:
                    held[name] = held.get(name, 0) + hi - lo
            name, ns = max(held.items(), key=lambda kv: kv[1])
            print(f"step {meta.get('step')} {(e - s) / 1e6:.3f} ms, most in "
                  f"{name} {ns / 1e6:.3f} ms")
    compiles = {n: spans.intervals(n, window) for n in COMPILE_EVENTS}
    lowered = compiles[LOWER_EVENT]
    out["compiles"] = len(lowered)
    out["compile_s"] = {n: sum(e - s for s, e, _ in evs) / 1e9
                        for n, evs in compiles.items()}
    out["compiled"] = sorted({m["function"] for _, _, m in lowered})
    print(f"compiles in the window: {len(lowered)} "
          f"({out['compile_s']}) of {out['compiled']}")
    return out


def run(argv=None, *, require_tpu: bool = True, overrides=None) -> dict:
    """One traced window of a cell; returns what :func:`report` found, with
    the window's end-to-end numbers under ``e2e``."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench.run import RunError, _device, start

    start()
    import jax

    from bench import spec
    from bench.trace import Trace

    cell = spec.Cell(args.workload, overrides=overrides)
    _device(cell.chips, require_tpu)
    loop = cell.loop().setup(cell, args.seed)
    gc.collect()
    gc.freeze()
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as log_dir:
        jax.profiler.start_trace(log_dir)
        try:
            e2e = loop.measure(args.seconds)
        finally:
            jax.profiler.stop_trace()
        trace = Trace.from_dir(log_dir)
        spans = ProgramSpans.from_dir(log_dir)
    gc.unfreeze()
    loop.release()
    print(f"end to end, traced: {e2e}")
    out = report(trace, spans)
    if out is None:
        raise RunError("the trace has no bench.window span")
    return dict(out, e2e=e2e)


def main() -> int:
    from bench.run import RunError

    try:
        out = run()
    except RunError as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
