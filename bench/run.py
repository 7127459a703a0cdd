#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run is one process: it loads the cell's files (``bench/spec.py``), sets
up the cell's traffic and programs from the seed and warms them
(``setup_s``), measures for ``--seconds``, reads the device's peak memory,
frees the program's state, and compares sampled outputs with the plain
reference. With ``--trace 1`` the window runs under the JAX profiler and
the cell's per-layer metrics are read from the trace, the harness's spans
and the program's counters, in place of its end-to-end metrics.

It fails, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``, each compared number
with its limit; the same numbers are the last lines on standard error.
``device`` also names the backend the window ran and how each request or
frame came out (``served``, ``retried``, ``degraded``, ...); only a first
try on the configured backend counts as served, and a window that fell
back to another backend gives no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import gc
import json
import os
import pathlib
import sys
import tempfile
from typing import Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent
PLATFORM = "tpu"


class RunError(RuntimeError):
    """The run cannot produce a result."""


def _import_program():
    """Put this checkout's ``src`` and root first on the path, and check
    that ``repro`` comes from there."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise RunError(f"no repro package under {src}")
    for p in (str(ROOT), str(src)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import repro

    if not pathlib.Path(repro.__file__).resolve().is_relative_to(src):
        raise RunError(f"repro imported from {repro.__file__}, not {src}")


def _device(chips: int, require_tpu: bool) -> dict:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:
        raise RunError(f"JAX found no usable device: {err}") from err
    d0 = devices[0]
    print(f"device: platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}")
    if require_tpu and d0.platform != PLATFORM:
        raise RunError(f"no {PLATFORM}: JAX runs on {d0.platform}")
    if len(devices) < chips:
        raise RunError(f"the cell needs {chips} chip(s); JAX has {len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def _peak_memory() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def _per_layer(cell, loop, trace, peaks) -> dict:
    from bench.spec import SpecError

    ctx = dict(record=loop.record, trace=trace, peaks=peaks,
               window=trace.window() if trace else None, cell=cell)
    out = {}
    for metric, reader in cell.metric_readers():
        value = reader.read(ctx)
        if value is None:
            print(f"per-layer {metric['name']}: nothing to read")
            continue
        if not isinstance(value, (int, float)):
            raise SpecError(f"{metric['name']} read {value!r}")
        out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def start() -> str:
    """Import the program from this checkout and keep every program it
    compiles in the checkout's persistent cache; returns the cache's path."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    _import_program()
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    # Keep every program of the cell, however quick to compile, so that
    # only a checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def one(cell, seed: int, seconds: float, *, trace: bool = False,
        reference=None, t_start: Optional[float] = None) -> dict:
    """One run of ``cell`` after :func:`start`: set-up and warm-up, the
    window, the device's peak memory, the program's state freed, and the
    sampled outputs compared with ``reference`` (the cell's own plain
    reference by default).

    Returns ``loop``, ``setup_s``, ``e2e`` (the window's end-to-end
    numbers), ``trace`` (a :class:`~bench.trace.Trace` or None),
    ``memory_peak_bytes``, ``checks`` and ``correct``, ``rows`` (each
    compared number with its limit). Raises :class:`RunError` where the
    window did not run the backend the configuration resolves to."""
    import jax

    from bench import compare
    from bench.trace import Trace

    t_start = time.perf_counter() if t_start is None else t_start
    t_cell = time.perf_counter()
    loop = cell.loop().setup(cell, seed)
    # What set-up made (imports, programs, traffic) lives as long as the
    # run: move it out of the collector's way, so that a full collection
    # in the window scans only what the window allocates.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    print(f"set-up {setup_s:.6f} s: {t_cell - t_start:.3f} s to reach the "
          f"device, {setup_s - (t_cell - t_start):.3f} s for the cell's "
          "traffic, programs and warm-up")

    tr = None
    if trace:
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as log_dir:
            jax.profiler.start_trace(log_dir)
            try:
                e2e = loop.measure(seconds)
            finally:
                jax.profiler.stop_trace()
            tr = Trace.from_dir(log_dir)
    else:
        e2e = loop.measure(seconds)
    gc.unfreeze()
    memory = _peak_memory()
    rec = loop.record
    print(f"backend: configured {rec['backend']}, ran {rec['backend_ran']}; "
          f"outcomes {rec['kinds']}")
    if rec["backend_ran"] != rec["backend"]:
        raise RunError(f"the window ran backend {rec['backend_ran']}, not "
                       f"{rec['backend']} as the configuration resolves: the "
                       "step fell back, so its timings are not the program's")

    loop.release()
    checks = compare.Checks()
    t_ref = time.perf_counter()
    loop.check(checks, reference if reference is not None else cell.reference())
    correct, rows = checks.verdict(cell.limits)
    print(f"reference comparison {time.perf_counter() - t_ref:.3f} s")
    return dict(loop=loop, setup_s=setup_s, e2e=e2e, trace=tr,
                memory_peak_bytes=memory, checks=checks, correct=correct,
                rows=rows)


def run(argv=None, *, require_tpu: bool = True, overrides=None) -> dict:
    """One run; returns the result object. Raises :class:`RunError` where
    there is no result to give."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = T_PROCESS if argv is None else time.perf_counter()

    cache = start()
    from bench import compare, spec

    cell = spec.Cell(args.workload, overrides=overrides)
    device = _device(cell.chips, require_tpu)
    peaks = spec.peaks_for(device["kind"]) if require_tpu else None
    print(f"cell {cell.name}: config {cell.entry['config']} traffic "
          f"{cell.entry['traffic']} seed {args.seed}; compilation cache {cache}")

    r = one(cell, args.seed, args.seconds, trace=bool(args.trace),
            t_start=t_start)
    loop, trace, rec = r["loop"], r["trace"], r["loop"].record
    device["memory_peak_bytes"] = r["memory_peak_bytes"]
    if args.trace:
        metrics = _per_layer(cell, loop, trace, peaks)
        window = trace.window()
        if window is not None:
            busy = trace.mean_busy_s(window)
            device["busy_s"] = busy if busy is not None else 0.0
            device["window_s"] = (window[1] - window[0]) / 1e9
    else:
        e2e = dict(r["e2e"], setup_s=r["setup_s"])
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise RunError(f"the run did not measure {m['name']}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device["backend"] = rec["backend_ran"]
    device["outcomes"] = dict(rec["kinds"])

    result = {
        "correct": bool(r["correct"]),
        "attempted": int(rec["attempted"]),
        "failed": int(rec["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace is not None and trace.window() is not None:
        w = trace.window()
        result["breakdown"] = {"device_ops": trace.top_ops(w),
                               "idle_gaps": trace.idle_gaps(w)}
    result["checks"] = compare.report(r["rows"])
    sys.stdout.flush()
    for line in compare.format_rows(r["rows"], r["checks"].frames):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    return result


def main() -> int:
    try:
        result = run()
    except RunError as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    print(json.dumps(result, allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
