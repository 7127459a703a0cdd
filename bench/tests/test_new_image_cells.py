"""A CPU rehearsal of the image cells that serve 12-bit u16 inspection
frames (``inspect12mp-mag``) and binary edge maps (``hd-batch-edges``):
whole short windows at tiny sizes, on the XLA backend and on the Pallas
interpreter, each ending ``correct``. Nothing here is a measurement."""
import json

import numpy as np
import pytest

from bench import run as bench_run
from bench import spec

# Ragged tiny frames: 61 rows and 203 columns are multiples of no tile.
TINY = {
    "inspect12mp-mag": {"image_h": 61, "image_w": 203},
    "hd-batch-edges": {"image_h": 64, "image_w": 128},
}


def _overrides(workload, backend):
    return {"fields": {**TINY[workload], "sobel_block_h": 0,
                       "sobel_block_w": 0, "sobel_backend": backend}}


@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("workload", sorted(TINY))
def test_whole_run_ends_correct(workload, backend):
    res = bench_run.run(
        ["--workload", workload, "--seed", str(2**31 + 11), "--seconds",
         "0.3", "--trace", "0"],
        require_tpu=False, overrides=_overrides(workload, backend))
    json.dumps(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["backend"] == backend
    cell = spec.Cell(workload)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    for name, row in res["checks"].items():
        assert row["value"] <= row["limit"], name


def test_traced_run_reports_the_image_metrics():
    res = bench_run.run(
        ["--workload", "inspect12mp-mag", "--seed", "5", "--seconds", "0.3",
         "--trace", "1"],
        require_tpu=False, overrides=_overrides("inspect12mp-mag", "xla"))
    names = {m["name"] for m in spec.Cell("inspect12mp-mag").per_layer}
    assert "kernel_ms.image" in names
    # Device readers find nothing on the CPU; the host's copy time does.
    assert "h2d_ms.image" in res["metrics"]
    assert set(res["metrics"]) <= names


def test_mono12_frames_span_the_sensor_range():
    """Every pooled frame reaches both ends of 0..4095, in u16, and none is
    an 8-bit scene in a wider container."""
    from bench.loops import closed_inspect

    pool = closed_inspect.requests(2**31 + 11, 3, 1, 96, 160, 12, 8.0,
                                   "uint16")
    assert len(pool) == 3
    for batch in pool:
        assert batch.dtype == np.uint16 and batch.shape == (1, 96, 160)
        assert batch.min() == 0 and batch.max() == 4095
        assert np.count_nonzero(batch > 255) > batch.size // 2
    assert not np.array_equal(pool[0], pool[1])
