#!/usr/bin/env python3
"""Find the knee of an open-loop stream cell: the most cameras it serves.

    python3 bench/sweep.py --workload cam1080-noisy --streams 4,8,12 --seconds 8

For each stream count, in one process, it runs the cell as ``bench/run.py``
does with that many cameras (the cell's own mix otherwise). A count is
sustained when every due frame is served, ``latency_p95_ms`` stays under
the frame period, and the generator's lateness does not grow from the
first half of the window to the second. The cells then run at about four
fifths of the highest sustained count, fixed in their traffic files.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.run import RunError, _device, one, start  # noqa: E402


def sweep(workload, counts, seconds, seed, *, require_tpu=True, overrides=None):
    start()
    from bench import spec

    rows = []
    for n in counts:
        ov = dict(overrides or {})
        ov["traffic"] = {**ov.get("traffic", {}), "streams": n}
        cell = spec.Cell(workload, overrides=ov)
        _device(cell.chips, require_tpu)
        r = one(cell, seed, seconds)
        e2e, rec = r["e2e"], r["loop"].record
        late = rec["lateness_s"]
        half = len(late) // 2
        growth_ms = (np.mean(late[half:]) - np.mean(late[:half] or [0])) * 1e3
        period_ms = 1e3 / r["loop"].fps
        row = dict(
            streams=n, failed=rec["failed"], attempted=rec["attempted"],
            p50_ms=e2e.get("latency_p50_ms"), p95_ms=e2e.get("latency_p95_ms"),
            lateness_growth_ms=growth_ms, period_ms=period_ms,
            correct=r["correct"],
        )
        row["sustained"] = bool(
            rec["failed"] == 0 and row["p95_ms"] is not None
            and row["p95_ms"] < period_ms and growth_ms < 1.0
        )
        print("sweep " + json.dumps(row), flush=True)
        rows.append(row)
        del r
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--streams", required=True, help="comma-separated counts")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    counts = [int(s) for s in args.streams.split(",") if s]
    try:
        sweep(args.workload, counts, args.seconds, args.seed)
    except RunError as err:
        print(f"sweep: {err}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
