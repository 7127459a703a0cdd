"""Exact integer lane of the megakernel: bit-exactness battery.

The contract is *exactness by construction*: the integer lane (u8 taps
accumulated in the i16/i32 dtype the ladder licenses, f32 only at
normalize/NMS) must be bit-identical to the f32 lane — every output
field, every operator, every padding, on ragged shapes, on both
backends, and under an 8-device mesh (subprocess case, mirrored from
test_halo_sharding; the CI multi-device job runs it directly).

``tests/test_lowprec_properties.py`` is the hypothesis twin (random
frames/geometry). No optional deps here (runs without hypothesis).
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from conftest import SUBPROCESS_TIMEOUT, slow_host

from repro.api import EdgeConfig, edge_detect
from repro.core import ladder
from repro.core.filters import get_operator, list_operators
from repro.kernels.dispatch import resolve_precision

PADDINGS = ("reflect", "edge", "zero")
FIELDS = ("magnitude", "components", "orientation", "peak", "thin", "edges")
FULL = dict(with_max=True, with_components=True, with_orientation=True)


def _gray(h=23, w=17, seed=0):
    return np.random.default_rng(seed).integers(0, 256, (h, w)).astype(np.uint8)


def _assert_bit_identical(out, ref, what):
    for f in FIELDS:
        a, b = getattr(out, f), getattr(ref, f)
        assert (a is None) == (b is None), (what, f)
        if a is not None:
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=str((what, f))
            )


# ---------------------------------------------------------------------------
# Integer lane == f32 lane, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("operator", list_operators())
def test_int_lane_bit_exact(operator, backend):
    # Every registered operator is int-eligible on gray u8 (DESIGN.md §11
    # bound table); keep that true or extend the lane deliberately.
    ok, _ = ladder.int_lane_eligible(get_operator(operator), rgb=False)
    assert ok, operator
    img = _gray()
    for padding in PADDINGS:
        base = EdgeConfig(operator=operator, backend=backend,
                          padding=padding, **FULL)
        ref = edge_detect(img, base.replace(precision="f32"))
        out = edge_detect(img, base.replace(precision="int"))
        _assert_bit_identical(out, ref, (operator, backend, padding))


def test_int_lane_batched_and_ragged_shapes():
    # Batch dim + deliberately unaligned H/W, with the NMS tail on top
    # (the thin map consumes integer-lane gradients through f32 atan2).
    for shape in ((8, 8), (23, 17), (64, 33), (2, 7, 40)):
        img = np.random.default_rng(1).integers(0, 256, shape).astype(np.uint8)
        base = EdgeConfig(operator="sobel5", backend="pallas-interpret",
                          nms=True, **FULL)
        ref = edge_detect(img, base.replace(precision="f32"))
        out = edge_detect(img, base.replace(precision="int"))
        _assert_bit_identical(out, ref, shape)


# ---------------------------------------------------------------------------
# Lane resolution + validation
# ---------------------------------------------------------------------------

def test_auto_precision_resolution():
    spec = get_operator("sobel3")
    u8 = np.dtype(np.uint8)
    # auto opts eligible gray-u8 in on Pallas only; XLA stays on the
    # measured f32 reference unless the user asks explicitly.
    for backend in ("pallas-interpret", "pallas-tpu"):
        assert resolve_precision("auto", backend, spec=spec, rgb=False,
                                 input_dtype=u8) == "int"
    assert resolve_precision("auto", "xla", spec=spec, rgb=False,
                             input_dtype=u8) == "f32"
    assert resolve_precision("int", "xla", spec=spec, rgb=False,
                             input_dtype=u8) == "int"
    # ineligible workloads: auto falls back, explicit raises with the gate
    assert resolve_precision("auto", "pallas-interpret", spec=spec, rgb=True,
                             input_dtype=u8) == "f32"
    f32 = np.dtype(np.float32)
    assert resolve_precision("auto", "pallas-interpret", spec=spec, rgb=False,
                             input_dtype=f32) == "f32"
    with pytest.raises(ValueError, match="precision='int' unavailable"):
        resolve_precision("int", "pallas-interpret", spec=spec, rgb=True,
                          input_dtype=u8)


def test_explicit_int_rejected_when_unprovable():
    rng = np.random.default_rng(3)
    rgb = rng.integers(0, 256, (9, 9, 3)).astype(np.uint8)
    gray_f32 = rng.random((9, 9)).astype(np.float32)
    for backend in ("xla", "pallas-interpret"):
        with pytest.raises(ValueError, match="precision='int' unavailable"):
            edge_detect(rgb, EdgeConfig(backend=backend, precision="int"))
        with pytest.raises(ValueError, match="precision='int' unavailable"):
            edge_detect(gray_f32, EdgeConfig(backend=backend, precision="int"))


def test_config_validation():
    # EdgeConfig validates in resolved() (the dispatch entry), not __init__
    with pytest.raises(ValueError, match="precision"):
        EdgeConfig(precision="fp8").resolved()


# ---------------------------------------------------------------------------
# 8-device mesh (subprocess; CI multi-device job runs this file directly)
# ---------------------------------------------------------------------------

def _run(script: str, timeout: int = SUBPROCESS_TIMEOUT) -> str:
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=timeout,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


INT_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np, jax
from repro.api import EdgeConfig, ShardConfig, edge_detect
from repro.core.filters import list_operators

assert len(jax.devices()) == 8
rng = np.random.default_rng(0)
x = rng.integers(0, 256, (3, 67, 45)).astype(np.uint8)   # ragged gray u8

def assert_same(out, ref, what):
    for f in ("magnitude", "components", "orientation", "peak", "thin",
              "edges"):
        a, b = getattr(out, f), getattr(ref, f)
        assert (a is None) == (b is None), (what, f)
        if a is not None:
            assert np.array_equal(np.asarray(a), np.asarray(b)), (what, f)

full = dict(nms=True, with_max=True, with_components=True,
            with_orientation=True)

# Integer lane under shard_map — batch and 2-D spatial meshes — vs the
#    single-device f32 fused reference: same bits, every operator.
for op in list_operators():
    ref = edge_detect(x, EdgeConfig(operator=op, backend="pallas-interpret",
                                    precision="f32", **full))
    for backend, shard in (
        ("xla", ShardConfig(data=8)),
        ("xla", ShardConfig(data=2, rows=2, cols=2)),
        ("pallas-interpret", ShardConfig(data=2, rows=2, cols=2)),
    ):
        out = edge_detect(x, EdgeConfig(operator=op, backend=backend,
                                        precision="int", shard=shard, **full))
        assert_same(out, ref, (op, backend, shard))
print("INT_SHARDED_OK")
"""


@pytest.mark.slow
@slow_host
def test_int_lane_sharded_bit_exact_8_devices():
    out = _run(INT_SHARDED)
    assert "INT_SHARDED_OK" in out, out
