"""StencilPlan: registry round-trips, gate-named validation, composed-halo
bit-exactness of fused multi-stage kernels.

The tentpole acceptance battery: a fused plan (Gaussian -> Sobel -> NMS)
is ONE Pallas launch whose outputs are bit-identical to the staged XLA
reference for every plan x padding x ragged shape — and, in the slow
subprocess case, on a forced 8-device sharded mesh. No optional deps
(runs without hypothesis).
"""
import dataclasses
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import SUBPROCESS_TIMEOUT, slow_host

from repro.api import EdgeConfig, edge_detect
from repro.core import filters as F

PADDINGS = ("reflect", "edge", "zero")
PLANS = ("canny5", "blur_sobel5")


def _img(rng, shape, dtype=np.float32):
    return jnp.asarray(rng.integers(0, 256, size=shape).astype(dtype))


# ---------------------------------------------------------------------------
# Registry + structure
# ---------------------------------------------------------------------------

def test_builtin_plan_registry():
    assert set(PLANS) <= set(F.list_plans())
    canny = F.get_plan("canny5")
    assert [s.name for s in canny.stages] == ["gaussian5", "sobel5", "nms"]
    assert canny.nms and canny.linear_reach == 4 and canny.reach == 5
    assert canny.gradient.name == "sobel5"
    assert canny.pre_stages[0].single_plane
    assert not canny.single_operator

    blur = F.get_plan("blur_sobel5")
    assert not blur.nms and blur.linear_reach == 4 and blur.reach == 4

    assert F.resolve_plan(None) is None
    assert F.resolve_plan("canny5") is canny
    assert F.resolve_plan(canny) is canny
    # identity = name + stage-signature hash (the TuneKey plan segment)
    ident = F.plan_identity(canny)
    assert ident.startswith("canny5.") and len(ident.split(".")[1]) == 8
    assert ident != F.plan_identity(blur)


def test_plan_is_jit_static():
    plan = F.get_plan("canny5")
    assert hash(plan) == hash(F.get_plan("canny5"))
    assert plan == F.make_plan("canny5", ("gaussian5", "sobel5", "nms"))


def test_gaussian_taps_are_exact_dyadic():
    """The binomial taps have power-of-two denominators, so the separable
    factors and the dense outer product are exact in f32 — the foundation
    of the plan bit-exactness claim."""
    g5 = F.get_stage("gaussian5").operator
    row = np.asarray(g5.sep[0][0], np.float64)
    np.testing.assert_array_equal(row * 16.0, [1.0, 4.0, 6.0, 4.0, 1.0])
    dense = np.asarray(g5.taps[0], np.float64)
    np.testing.assert_array_equal(dense, np.outer(row, row))


# ---------------------------------------------------------------------------
# Gate-named validation (each error names the failing gate)
# ---------------------------------------------------------------------------

def test_gate_unknown_stage():
    with pytest.raises(ValueError, match="plan gate 'unknown-stage'"):
        F.make_plan("p", ("no-such-stage", "sobel5"))


def test_gate_frozen_stage():
    @dataclasses.dataclass  # not frozen — unhashable as a jit static
    class MutableStage:
        name: str = "mut"
        kind: str = "pointwise"
        radius: int = 0

    with pytest.raises(ValueError, match="plan gate 'frozen-stage'"):
        F.StencilPlan(name="p", stages=(MutableStage(),))


def test_gate_window_radius():
    with pytest.raises(ValueError, match="plan gate 'window-radius'"):
        F.window_stage("null-window", "max", 0)


def test_gate_nms_not_last():
    with pytest.raises(ValueError, match="plan gate 'nms-last'"):
        F.make_plan("p", ("nms", "sobel5"))


def test_gate_nms_without_gradient():
    with pytest.raises(ValueError, match="plan gate 'nms-gradient'"):
        F.make_plan("p", ("gaussian5", "nms"))


def test_gate_gradient_not_last():
    with pytest.raises(ValueError, match="plan gate 'gradient-last'"):
        F.make_plan("p", ("sobel5", "gaussian5"))


def test_gate_empty_plan():
    with pytest.raises(ValueError, match="plan gate 'empty-plan'"):
        F.StencilPlan(name="p", stages=())


def test_gate_unknown_plan():
    with pytest.raises(ValueError, match="plan gate 'unknown-plan'"):
        EdgeConfig(plan="no-such-plan").resolved()


def test_gate_nms_requested_without_nms_stage():
    with pytest.raises(ValueError, match="plan gate 'nms-stage'"):
        EdgeConfig(plan="blur_sobel5", nms=True).resolved()
    with pytest.raises(ValueError, match="plan gate 'nms-stage'"):
        EdgeConfig(plan="blur_sobel5", hysteresis=True).resolved()


def test_gate_integer_taps(rng):
    """precision="int" with a fractional-tap pre-stage must raise with the
    failing gate — the Gaussian's /16 taps are exact in f32 but not
    representable in the integer lane."""
    img = jnp.asarray(rng.integers(0, 256, (1, 32, 48)).astype(np.uint8))
    with pytest.raises(ValueError, match="plan gate 'integer-taps'"):
        edge_detect(img, EdgeConfig(plan="canny5", precision="int",
                                    backend="pallas-interpret",
                                    block_h=8, block_w=16))


def test_streaming_rejects_multistage_plan(rng):
    from repro import api

    cfg = EdgeConfig(plan="canny5", backend="pallas-interpret",
                     block_h=8, block_w=16)
    with pytest.raises(ValueError, match="stream path"):
        state = api.StreamState.init(1, 32, 48, cfg)
        frames = _img(rng, (1, 32, 48))
        api.edge_detect_stream(frames, cfg, state)


# ---------------------------------------------------------------------------
# Facade threading
# ---------------------------------------------------------------------------

def test_resolved_pins_operator_and_nms():
    cfg = EdgeConfig(plan="canny5", operator="sobel3").resolved()
    assert cfg.operator == "sobel5"  # plan.gradient wins over the field
    assert cfg.nms is True           # forced by the trailing nms stage
    assert cfg.spec is F.get_plan("canny5").gradient
    cfg2 = EdgeConfig(plan="blur_sobel5").resolved()
    assert cfg2.operator == "sobel5" and cfg2.nms is False


def test_exchange_radius_composes():
    from repro.kernels.tiling import window_radius
    from repro.sharding import halo

    canny = F.get_plan("canny5")
    spec = canny.gradient
    assert halo.exchange_radius(spec, False, plan=canny) == 5  # 2+2+1
    assert halo.exchange_radius(spec, False) == spec.radius
    assert window_radius(canny.linear_reach, canny.nms) == 5


# ---------------------------------------------------------------------------
# Bit-exactness: fused Pallas vs staged XLA reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("padding", PADDINGS)
@pytest.mark.parametrize("plan", PLANS)
def test_fused_plan_matches_staged_xla(plan, padding, rng):
    """The acceptance bar: one fused launch == the staged XLA reference,
    byte for byte, on ragged shapes, every padding, every output field."""
    for shape in ((1, 37, 53), (2, 64, 41)):
        img = _img(rng, shape)
        base = EdgeConfig(plan=plan, padding=padding, with_max=True,
                          hysteresis=(plan == "canny5"))
        ref = edge_detect(img, base.replace(backend="xla"))
        out = edge_detect(img, base.replace(backend="pallas-interpret",
                                            block_h=8, block_w=16))
        for f in ("magnitude", "peak", "thin", "edges"):
            a, b = getattr(out, f), getattr(ref, f)
            assert (a is None) == (b is None), (plan, padding, shape, f)
            if a is not None:
                assert np.array_equal(np.asarray(a), np.asarray(b)), (
                    plan, padding, shape, f)


def test_fused_plan_block_shape_invariance(rng):
    """The composed halo must make the fused plan tile-geometry-proof."""
    img = _img(rng, (1, 96, 80))
    cfg = EdgeConfig(plan="canny5", backend="pallas-interpret")
    outs = [
        np.asarray(edge_detect(img, cfg.replace(block_h=bh, block_w=bw)).magnitude)
        for bh, bw in ((8, 16), (16, 80), (32, 32), (96, 80))
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


@pytest.mark.parametrize("padding", PADDINGS)
def test_composed_extension_matches_textbook_staging_interior(padding, rng):
    """Composed extension (pad raw input ONCE by the total reach) equals
    textbook per-stage staging (re-pad each intermediate plane) at every
    interior pixel — they may only differ inside the boundary band, where
    staged re-padding reflects/replicates *blurred* values instead of raw
    ones."""
    from repro.core.sobel import _pad, _stage_apply, magnitude, spec_components

    img = _img(rng, (1, 48, 57))
    plan = F.get_plan("blur_sobel5")
    # textbook: blur with its own pad, then gradient with its own pad
    blur_stage = plan.pre_stages[0]
    ext, h, w = _pad(img, blur_stage.radius, padding)
    blurred = _stage_apply(ext, blur_stage, h, w)
    ext2, _, _ = _pad(blurred, plan.gradient.radius, padding)
    comps = spec_components(ext2, plan.gradient, h, w, "v2",
                            max(plan.gradient.directions))
    staged = np.asarray(magnitude(comps))
    fused = np.asarray(edge_detect(img, EdgeConfig(
        plan=plan, padding=padding, normalize=False,
        backend="pallas-interpret", block_h=16, block_w=19)).magnitude)
    R = plan.linear_reach
    np.testing.assert_array_equal(fused[:, R:-R, R:-R], staged[:, R:-R, R:-R])


def test_single_stage_plan_collapses_to_operator_path(rng):
    """A plan that is exactly one gradient stage takes the historical
    single-operator kernel path — outputs byte-identical to the plain
    operator config on both backends."""
    plan = F.make_plan("solo5", ("sobel5",))
    assert plan.single_operator
    img = _img(rng, (2, 45, 61))
    for backend in ("xla", "pallas-interpret"):
        cfg = EdgeConfig(backend=backend, block_h=8, block_w=16)
        a = edge_detect(img, cfg.replace(plan=plan)).magnitude
        b = edge_detect(img, cfg.replace(operator="sobel5")).magnitude
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_single_launch_and_analyzer_battery():
    """FUSE002 on the real trace: the whole canny5 chain is ONE
    pallas_call, via the analyzer's plan battery."""
    from repro.analysis import analyze

    report = analyze(operators=("sobel5",), modes=("plain",),
                     backends=("pallas-interpret",), layouts=("gray",),
                     plans=("canny5",), export=False)
    assert report.ok, [str(v) for v in report.violations]
    assert "plan:canny5/pallas-interpret/reflect/gray" in report.combos


def test_plan_autotune_lands_in_plan_slot(tmp_path):
    from repro.kernels import tuning

    cache = tuning.TuningCache(str(tmp_path / "blocks.json"))
    bh, bw = tuning.autotune(32, 48, plan="canny5", shapes=[(8, 16)],
                             iters=1, cache=cache, save=False)
    assert (bh, bw) == (8, 16)
    key = tuning.TuneKey(
        "pallas-interpret", "float32", "sobel5", "v2", 32, 48,
        plan=F.plan_identity(F.get_plan("canny5")))
    assert cache.lookup(key) == (8, 16)
    # the single-operator slot is untouched
    assert cache.lookup(tuning.TuneKey(
        "pallas-interpret", "float32", "sobel5", "v2", 32, 48)) is None


# ---------------------------------------------------------------------------
# Multi-device (slow subprocess battery, 8 faked host devices)
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


SHARDED_PLANS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np, jax
from repro.api import EdgeConfig, ShardConfig, edge_detect

assert len(jax.devices()) == 8

rng = np.random.default_rng(7)
x = rng.integers(0, 256, (3, 67, 45)).astype(np.float32)   # ragged H/W

for plan in ("canny5", "blur_sobel5"):
    base = EdgeConfig(plan=plan, with_max=True,
                      hysteresis=(plan == "canny5"))
    ref = edge_detect(x, base.replace(backend="pallas-interpret"))
    for shard in (ShardConfig(data=8), ShardConfig(data=2, rows=2, cols=2)):
        out = edge_detect(x, base.replace(backend="xla", shard=shard))
        for f in ("magnitude", "peak", "thin", "edges"):
            a, b = getattr(out, f), getattr(ref, f)
            assert (a is None) == (b is None), (plan, shard, f)
            if a is not None:
                assert np.array_equal(np.asarray(a), np.asarray(b)), (
                    plan, shard, f)
print("PLAN_SHARDED_OK")
"""


@pytest.mark.slow
@slow_host
def test_sharded_plan_bit_exact_8_devices():
    out = _run(SHARDED_PLANS)
    assert "PLAN_SHARDED_OK" in out, out
