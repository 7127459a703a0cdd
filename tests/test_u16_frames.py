"""12/16-bit gray frames (``uint16``) through the image path.

The kernels read u16 frames raw and widen them per block, in either lane;
the integer lane's accumulator is proven from the frame's dtype (65535 for
u16). Every served magnitude is compared with the staged XLA reference on
the frame cast to f32 (exact: 65535 < 2^24) and with the benchmark's plain
reference, on ragged sizes, on 12-bit scenes, uniform 16-bit noise and a
0/65535 checkerboard.
"""
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EdgeConfig, edge_detect
from repro.configs import get_config
from repro.core import ladder
from repro.core.sobel import sobel as staged_sobel
from repro.core.filters import get_operator
from repro.data.synthetic import image_batch
from repro.kernels import dispatch
from repro.kernels import edge as ek

H, W = 61, 203          # ragged: a multiple of no tile


def _bench_reference():
    path = pathlib.Path(__file__).resolve().parents[1] / "bench/references/sobel5.py"
    spec = importlib.util.spec_from_file_location("bench_reference_sobel5", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _frames(kind):
    rng = np.random.default_rng(1612)
    if kind == "scene12":
        cfg = get_config("inspect-12mp", smoke=True)
        return image_batch(cfg, 2, seed=5)["images"]
    if kind == "uniform16":
        return rng.integers(0, 65536, (2, H, W)).astype(np.uint16)
    board = (np.indices((H, W)).sum(0) % 2) * 65535
    return board.astype(np.uint16)[None]


KINDS = ["scene12", "uniform16", "checker"]
PATHS = [("xla", "f32"), ("xla", "int"), ("pallas-interpret", "f32"),
         ("pallas-interpret", "int")]


@pytest.mark.parametrize("backend,precision", PATHS,
                         ids=[f"{b}-{p}" for b, p in PATHS])
@pytest.mark.parametrize("kind", KINDS)
def test_u16_magnitude_bit_identical_to_xla_reference(kind, backend, precision):
    img = _frames(kind)
    got = edge_detect(img, EdgeConfig(normalize=False, backend=backend,
                                      precision=precision, block_h=16,
                                      block_w=128))
    want = staged_sobel(jnp.asarray(img).astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(got.magnitude), np.asarray(want))


@pytest.mark.parametrize("precision", ["f32", "int"])
@pytest.mark.parametrize("kind", KINDS)
def test_u16_served_magnitude_matches_bench_reference(kind, precision):
    """The served form (peak-normalised, as ``launch/serve`` builds it)
    within the benchmark's ``mag_err`` limit of its plain reference."""
    img = _frames(kind)
    cfg = get_config("inspect-12mp", smoke=True).replace(
        sobel_backend="pallas-interpret")
    from repro.launch.serve import image_edge_config

    ecfg = image_edge_config(cfg).replace(precision=precision)
    got = np.asarray(edge_detect(img, ecfg).magnitude)
    want = np.asarray(_bench_reference().outputs(jnp.asarray(img),
                                                 edges=False)["magnitude"])
    assert np.max(np.abs(got - want)) / 255.0 <= 1e-4


@pytest.mark.parametrize("precision", ["f32", "int"])
def test_u16_edges_match_xla(precision):
    img = _frames("scene12")
    cfg = EdgeConfig(nms=True, hysteresis=True, precision=precision)
    got = edge_detect(img, cfg.replace(backend="pallas-interpret",
                                       block_h=16, block_w=128))
    want = edge_detect(img.astype(np.float32), cfg.replace(backend="xla",
                                                           precision="f32"))
    np.testing.assert_array_equal(np.asarray(got.edges), np.asarray(want.edges))
    np.testing.assert_array_equal(np.asarray(got.magnitude),
                                  np.asarray(want.magnitude))


def test_kernel_reads_u16_raw():
    x = jnp.zeros((1, H, W), jnp.uint16)
    assert ek.kernel_dtype(x).dtype == jnp.uint16
    assert ek.kernel_dtype(x.astype(jnp.int16)).dtype == jnp.float32
    jaxpr = jax.make_jaxpr(lambda a: edge_detect(
        a, EdgeConfig(backend="pallas-interpret", block_h=16, block_w=128)
    ).magnitude)(x)
    calls = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
             if e.primitive.name == "pallas_call"]
    assert [c.invars[0].aval.dtype for c in calls] == [jnp.uint16]


def test_kernel_names_say_dtype_and_lane():
    assert ek.kernel_name(jnp.uint16, "int32") == "edge_u16_int32"
    assert ek.kernel_name(jnp.float32, "f32") == "edge_f32_f32"
    assert ek.kernel_name(jnp.uint8, "f32", stream=True) == "edge_stream_u8_f32"


def test_u16_has_its_own_tuning_dtype():
    u16 = jnp.zeros((1, 8, 8), jnp.uint16)
    assert dispatch._kernel_dtype_name(u16) == "uint16"
    assert dispatch._kernel_dtype_name(u16.astype(jnp.uint8)) == "uint8"
    assert dispatch._kernel_dtype_name(u16.astype(jnp.int16)) == "float32"


def test_u16_sobel5_is_eligible_with_i32():
    spec = get_operator("sobel5")
    assert ladder.frame_max("uint16") == 65535
    assert ladder.int_lane_eligible(spec, rgb=False, input_dtype="uint16") == (
        True, "")
    assert ladder.accum_dtype(spec, input_dtype="uint16") == "int32"
    bounds = ladder.tap_accumulation_bounds(spec, input_max=65535)
    assert bounds["worst"] == 12_582_720 and bounds["f32_exact"]
    # sobel3 needs i16 at u8, i32 at u16
    s3 = get_operator("sobel3")
    assert ladder.accum_dtype(s3, input_dtype="uint8") == "int16"
    assert ladder.accum_dtype(s3, input_dtype="uint16") == "int32"


@pytest.mark.parametrize("dtype", ["int16", "int32", "uint32", "float32"])
def test_other_frame_dtypes_are_ineligible(dtype):
    ok, reason = ladder.int_lane_eligible(get_operator("sobel5"), rgb=False,
                                          input_dtype=dtype)
    assert not ok
    assert f"input dtype {dtype} is not uint8/uint16" in reason
    assert ladder.accum_dtype(get_operator("sobel5"), input_dtype=dtype) is None


def test_sobel7_keeps_the_int_lane_at_u8_only():
    spec = get_operator("sobel7")
    assert ladder.int_lane_eligible(spec, rgb=False, input_dtype="uint8")[0]
    ok, reason = ladder.int_lane_eligible(spec, rgb=False, input_dtype="uint16")
    assert not ok and "83884800" in reason and "2^24" in reason
    assert ladder.tap_accumulation_bounds(spec, input_max=65535)["worst"] == (
        83_884_800)
    assert dispatch.resolve_precision(
        "auto", "pallas-interpret", spec=spec, rgb=False, input_dtype="uint16"
    ) == "f32"
    with pytest.raises(ValueError, match="2\\^24"):
        edge_detect(np.zeros((1, 32, 32), np.uint16),
                    EdgeConfig(operator="sobel7", precision="int"))


def test_plan_gates_take_the_frame_dtype():
    from repro.core.filters import make_plan

    dilate = make_plan("dilate_sobel3", ("dilate3", "sobel3"))
    assert ladder.plan_accum_dtype(dilate, input_dtype="uint8") == "int16"
    assert ladder.plan_accum_dtype(dilate, input_dtype="uint16") == "int32"
    assert ladder.plan_int_eligible(dilate, rgb=False,
                                    input_dtype="uint16") == (True, "")
    tall = make_plan("dilate_sobel7", ("dilate3", "sobel7"))
    assert ladder.plan_int_eligible(tall, rgb=False, input_dtype="uint8")[0]
    ok, reason = ladder.plan_int_eligible(tall, rgb=False, input_dtype="uint16")
    assert not ok and "2^24" in reason
    ok, reason = ladder.plan_int_eligible(dilate, rgb=False,
                                          input_dtype="int32")
    assert not ok and "input dtype int32" in reason


def test_inspect_config_makes_12bit_u16_frames():
    cfg = get_config("inspect-12mp")
    assert (cfg.image_h, cfg.image_w, cfg.frame_bits) == (3000, 4096, 12)
    assert cfg.frame_dtype == "uint16"
    assert get_config("sobel-hd").frame_dtype == "float32"
    imgs = image_batch(get_config("inspect-12mp", smoke=True), 3)["images"]
    assert imgs.dtype == np.uint16 and imgs.shape == (3, H, W)
    assert imgs.min() == 0 and imgs.max() == 4095
    # the float scene of sobel-hd is unchanged by the integer path
    f = image_batch(get_config("sobel-hd", smoke=True), 1)["images"]
    assert f.dtype == np.float32 and f.max() <= 255.0


def test_serve_cli_serves_u16_frames(capsys):
    from repro.launch import serve

    args = serve.build_parser().parse_args(
        ["--arch", "inspect-12mp", "--smoke", "--requests", "2"])
    cfg = get_config("inspect-12mp", smoke=True)
    seen = []
    rep = serve.serve_image(cfg, args, on_result=lambda req, out: seen.append(
        out.magnitude.shape))
    assert len(rep["lat_ms"]) == 2 and rep["health"].unaccounted == 0
    assert not rep["health"].degraded
    assert seen and all(s[-2:] == (H, W) for s in seen)
    assert "uint16" in capsys.readouterr().out
