"""Where the benchmark's data lives, and how a run finds it by name.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

  bench/configs/<config>.json      the deployment: repro config fields,
                                   frame dtype, reference, source
  bench/traffic/<traffic>.json     a mix: parameters for one loop
  bench/loops/<loop>.py            one serving loop per kind of traffic
  bench/references/<name>.py       a plain reference of the outputs
  bench/limits/<workload>.json     the limit of each compared number
  bench/metrics/<metric>.py        one reader per per-layer metric
  bench/peaks.json                 peak rates keyed by ``device_kind``

A later cell, configuration, mix or metric is new files and new
``BENCHMARK.json`` entries; no file here needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Any, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(ValueError):
    """The benchmark's data does not name what a run needs."""


def _load_json(path: pathlib.Path) -> Any:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """Import ``bench/<kind>/<name>.py`` by path (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Peak rates of ``device_kind``; an unknown device is an error."""
    table = _load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise SpecError(f"no peaks for device_kind {device_kind!r} in "
                        f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded.

    ``overrides`` (tests only) updates the loaded ``config`` fields and
    ``traffic`` parameters, e.g. to run a tiny frame on the CPU.
    """

    def __init__(self, workload: str, *, overrides: Optional[dict] = None):
        bm = _load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bm["workloads"]}
        if workload not in cells:
            raise SpecError(f"unknown workload {workload!r}; known: "
                            f"{sorted(cells)}")
        self.name = workload
        self.entry = cells[workload]
        self.chips = int(self.entry["chips"])
        self.config = _load_json(BENCH / "configs" / f"{self.entry['config']}.json")
        self.traffic = _load_json(BENCH / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = _load_json(BENCH / "limits" / f"{workload}.json")
        if overrides:
            self.config["fields"].update(overrides.get("fields", {}))
            self.traffic = {**self.traffic, **overrides.get("traffic", {})}
        self.end_to_end = [m for m in bm["end_to_end"] if self._has(m)]
        self.per_layer = [m for m in bm["per_layer"] if self._has(m)]

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def repro_config(self):
        """The ``repro`` config the cell runs: a registered base with the
        configuration file's fields."""
        from repro.configs import get_config

        return get_config(self.config["base"]).replace(**self.config["fields"])

    def loop(self):
        return load_module("loops", self.traffic["loop"])

    def reference(self):
        return load_module("references", self.config["reference"])

    def metric_readers(self) -> List[tuple]:
        return [(m, load_module("metrics", m["name"])) for m in self.per_layer]
