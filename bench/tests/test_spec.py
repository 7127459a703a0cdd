"""BENCHMARK.json names only what exists, and each cell reports what the
contract asks of it."""
import json
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BM = json.loads((spec.ROOT / "BENCHMARK.json").read_text())


def test_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BM[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" for m in BM["end_to_end"])


@pytest.mark.parametrize("workload", [w["name"] for w in BM["workloads"]])
def test_cell_files_and_metrics(workload):
    cell = spec.Cell(workload)
    assert cell.config["name"] == cell.entry["config"]
    assert hasattr(cell.loop(), "setup")
    assert hasattr(cell.reference(), "outputs")
    assert cell.limits
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for metric, reader in cell.metric_readers():
        assert callable(reader.read)
        assert metric["moves"] in e2e, (metric["name"], workload)


def test_config_files_match_their_entries():
    for c in BM["configs"]:
        data = json.loads((spec.ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in BM["workloads"])


def test_peaks_by_device_kind():
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="no peaks"):
        spec.peaks_for("TPU v9 imaginary")


def test_unknown_workload_is_refused():
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.Cell("no-such-cell")
