"""Multi-device edge engine: shard_map batch + spatial halo-exchange
parallelism is bit-exact with the single-device fused path, and the serve
loop survives a device-loss reshard.

The multi-device cases run in a subprocess with 8 faked host devices
(XLA_FLAGS must be set before jax initializes); the CI multi-device job
runs this file directly. Geometry/planning units run in-process.
"""
import os
import subprocess
import sys

import pytest
from conftest import SUBPROCESS_TIMEOUT, slow_host


def _run(script: str, timeout: int = SUBPROCESS_TIMEOUT) -> str:
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=timeout,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


BIT_EXACT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np, jax, jax.numpy as jnp
from repro.api import EdgeConfig, ShardConfig, edge_detect
from repro.core.filters import list_operators
from repro.sharding.halo import mesh_from_config

assert len(jax.devices()) == 8

rng = np.random.default_rng(0)
x = rng.integers(0, 256, (3, 67, 45)).astype(np.float32)   # ragged H/W

def assert_same(out, ref, what):
    for f in ("magnitude", "components", "orientation", "peak", "thin",
              "edges"):
        a, b = getattr(out, f), getattr(ref, f)
        assert (a is None) == (b is None), (what, f)
        if a is not None:
            assert np.array_equal(np.asarray(a), np.asarray(b)), (what, f)

# 1) Every registered operator: batch-sharded AND 2-D spatially sharded
#    (xla under shard_map) vs the single-device *fused* path.
for op in list_operators():
    ref = edge_detect(x, EdgeConfig(operator=op, backend="pallas-interpret",
                                    with_max=True))
    for shard in (ShardConfig(data=8), ShardConfig(data=2, rows=2, cols=2)):
        out = edge_detect(x, EdgeConfig(operator=op, backend="xla",
                                        with_max=True, shard=shard))
        assert_same(out, ref, (op, shard))
print("OPERATORS_OK")

# 2) The fused Pallas kernel itself under shard_map: paddings x mesh shapes,
#    with components/orientation, on ragged shapes.
full = dict(with_max=True, with_components=True, with_orientation=True)
for padding in ("reflect", "edge", "zero"):
    ref = edge_detect(x, EdgeConfig(backend="pallas-interpret",
                                    padding=padding, **full))
    for shard in (ShardConfig(data=2, rows=2, cols=2),
                  ShardConfig(data=1, rows=4, cols=2)):
        out = edge_detect(x, EdgeConfig(backend="pallas-interpret",
                                        padding=padding, shard=shard, **full))
        assert_same(out, ref, (padding, shard))
print("PALLAS_SHARDED_OK")

# 3) RGB u8 fused megakernel, jitted, with an explicit mesh (the serve path).
xrgb = rng.integers(0, 256, (3, 50, 41, 3)).astype(np.uint8)
cfg = EdgeConfig(backend="pallas-interpret", with_max=True)
ref = edge_detect(xrgb, cfg)
mesh = mesh_from_config(ShardConfig(data=2, rows=2, cols=2))
out = jax.jit(lambda f: edge_detect(f, cfg, mesh=mesh))(jnp.asarray(xrgb))
assert_same(out, ref, "rgb-jit-mesh")
print("RGB_JIT_OK")

# 4) Edge maps: fused NMS + post-gather hysteresis — the device-level halo
#    grows to radius+1 and linking runs on the gathered thin map, so sharded
#    thin/edges must be bit-identical to single-device for both backends.
nmsfull = dict(nms=True, hysteresis=True, with_max=True,
               with_components=True, with_orientation=True)
for backend in ("xla", "pallas-interpret"):
    for padding in ("reflect", "edge", "zero"):
        ref = edge_detect(x, EdgeConfig(backend=backend, padding=padding,
                                        **nmsfull))
        for shard in (ShardConfig(data=8),
                      ShardConfig(data=2, rows=2, cols=2),
                      ShardConfig(data=1, rows=4, cols=2)):
            out = edge_detect(x, EdgeConfig(backend=backend, padding=padding,
                                            shard=shard, **nmsfull))
            assert_same(out, ref, ("nms", backend, padding, shard))
print("NMS_SHARDED_OK")

# 5) Spatial shard too small for the halo -> actionable error.
tiny = rng.integers(0, 256, (1, 8, 8)).astype(np.float32)
try:
    edge_detect(tiny, EdgeConfig(operator="sobel7", backend="xla",
                                 shard=ShardConfig(data=1, rows=4, cols=1)))
except ValueError as e:
    assert "too small for operator radius" in str(e), e
else:
    raise AssertionError("expected ValueError for too-fine spatial grid")
print("VALIDATION_OK")
"""


@pytest.mark.slow
@slow_host
def test_sharded_bit_exact_8_devices():
    out = _run(BIT_EXACT)
    for marker in ("OPERATORS_OK", "PALLAS_SHARDED_OK", "RGB_JIT_OK",
                   "NMS_SHARDED_OK", "VALIDATION_OK"):
        assert marker in out, out


SERVE_LOSS = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.argv = ["serve", "--arch", "sobel-hd", "--smoke", "--requests", "6",
            "--slots", "2", "--shard", "2x2x2", "--simulate-loss-at", "3"]
from repro.launch.serve import main
main()
"""


@pytest.mark.slow
@slow_host
def test_serve_survives_device_loss():
    out = _run(SERVE_LOSS)
    assert "device loss: 8 -> 4 devices" in out, out
    assert "data=1 row=2 col=2" in out, out       # spatial grid survived
    assert "served through reshard" in out, out   # traffic run completed


# ---------------------------------------------------------------------------
# Geometry / planning units (single device, in-process)
# ---------------------------------------------------------------------------

def test_shard_geometry():
    from repro.sharding.halo import shard_geometry

    assert shard_geometry(64, 1, 2) == (64, 64)        # unsharded: identity
    sh, hp = shard_geometry(67, 2, 2)                  # ragged split
    assert sh * 2 == hp and hp >= 67 + 2               # radius of slack
    sh, hp = shard_geometry(64, 4, 2)                  # divisible still pads
    assert hp >= 64 + 2 and hp % 4 == 0


def test_shard_config_parse_and_resolve():
    from repro.api import ShardConfig

    assert ShardConfig.parse("2x2x2") == ShardConfig(data=2, rows=2, cols=2)
    assert ShardConfig.parse("auto") == ShardConfig.auto()
    assert ShardConfig.parse("0x4x2").resolve(8) == (1, 4, 2)
    assert ShardConfig(data=0).resolve(8) == (8, 1, 1)  # auto-fill data
    with pytest.raises(ValueError):
        ShardConfig.parse("2x2")
    with pytest.raises(ValueError):
        ShardConfig(data=1, rows=4, cols=4).resolve(8)  # spatial > devices
    with pytest.raises(ValueError):
        ShardConfig(data=4, rows=2, cols=2).resolve(8)  # explicit total > devices
    with pytest.raises(ValueError):
        ShardConfig(data=2, rows=0, cols=2).resolve(8)  # zero spatial degree


def test_plan_image_mesh_shrinks_data_first():
    from repro.runtime.elastic import plan_image_mesh

    shape, axes = plan_image_mesh(8, rows=2, cols=2)
    assert shape == (2, 2, 2) and axes == ("data", "row", "col")
    # device loss: spatial grid survives, data shrinks
    assert plan_image_mesh(4, rows=2, cols=2)[0] == (1, 2, 2)
    # only when survivors cannot carry the grid does spatial shrink
    assert plan_image_mesh(2, rows=2, cols=2)[0] == (1, 1, 2)
    assert plan_image_mesh(1, rows=2, cols=2)[0] == (1, 1, 1)


def test_single_device_shard_config_is_identity(rng):
    """A 1x1x1 shard resolves to the plain single-device engine."""
    import numpy as np

    from repro.api import EdgeConfig, ShardConfig, edge_detect

    x = rng.integers(0, 256, (2, 33, 41)).astype(np.float32)
    ref = edge_detect(x, EdgeConfig(backend="xla"))
    out = edge_detect(x, EdgeConfig(backend="xla",
                                    shard=ShardConfig(data=1)))
    assert np.array_equal(np.asarray(out.magnitude), np.asarray(ref.magnitude))


def test_image_rules_and_specs():
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.sharding.partition import image_spec, layout_logical_axes
    from repro.sharding.rules import logical_to_spec

    mesh = AbstractMesh((2, 2, 2), ("data", "row", "col"))

    assert layout_logical_axes("NHWC") == ("batch", "height", "width", "channel")
    assert layout_logical_axes("NTHW") == ("batch", None, "height", "width")
    spec = logical_to_spec(("batch", "height", "width"), mesh, (8, 64, 64))
    assert spec == P("data", "row", "col")
    assert image_spec("NHWC", mesh, (8, 64, 64, 3)) == P("data", "row", "col")

    # image batches on the legacy LM mesh still spread their rows
    lm = AbstractMesh((4, 2), ("data", "model"))
    assert logical_to_spec(("batch", "height", "width"), lm, (8, 64, 64)) == P(
        "data", "model"
    )
