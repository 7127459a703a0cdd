"""Compile rehearsals of the main-path kernels for a described TPU v5e.

Interpret mode runs on the CPU and never asks Mosaic (the TPU kernel
compiler) anything, so a kernel can pass every bit-exactness test and still
be refused on the chip: an unaligned window, an unsupported cast, a vector
layout Mosaic cannot lower. These tests compile each kernel of the main path
at its real size for one chip of a described ``v5e:2x2`` — the TPU compiler
is installed even where no chip is attached — and fail where the chip's
compiler would. Nothing runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture (never while a
module is imported): only the process that runs these tests loads the TPU
library. The persistent compilation cache is off around the compiles, since
an entry written for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import edge as ek

BH, BW = 64, 256          # sobel-hd's pinned blocks (configs/sobel_hd.py)
HD = (4, 2048, 2048)      # sobel-hd: 4 frames of 2048x2048 per request
P1080 = (1, 1080, 1920)   # ragged rows: 1080 is not a multiple of 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(topo, fn, *shapes):
    """Compile ``fn`` for one described chip; returns the HLO text."""
    one = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel, not an XLA fallback
    return text


@pytest.mark.parametrize(
    "shape,dtype,kw",
    [
        # sobel-hd image serving: f32 frames, magnitude + per-block maxima
        (HD, jnp.float32, dict(with_max=True)),
        # --edges: fused NMS on u8 frames
        (HD, jnp.uint8, dict(out_nms=True, with_max=True)),
        # exact integer lane (u8 -> i16/i32 widening)
        (HD, jnp.uint8, dict(precision="int", with_max=True)),
        # manual HBM->VMEM DMA ring
        (HD, jnp.uint8, dict(pipeline_depth=2, with_max=True)),
        # fused multi-stage plan at 1080p (ragged row blocks)
        (P1080, jnp.uint8, dict(plan="canny5", out_nms=True, with_max=True)),
        # interleaved RGB u8 at 1080p (planar windows + in-kernel luma)
        (P1080 + (3,), jnp.uint8, dict(rgb=True, with_max=True)),
    ],
    ids=["sobel_hd_f32_mag_max", "nms_u8", "int_lane_u8", "dma_ring_d2",
         "canny5_1080p", "rgb_u8_1080p"],
)
def test_edge_pallas_compiles_for_v5e(topo, shape, dtype, kw):
    _compile(
        topo,
        lambda x: ek.edge_pallas(x, block_h=BH, block_w=BW, **kw),
        (shape, dtype),
    )


def test_stream_kernel_compiles_for_v5e(topo):
    """The masked delta-skip kernel, as the stream engine runs it: 2 u8
    streams at 2048^2 with fused NMS and cached maxima spliced in."""
    n, h, w = 2, 2048, 2048
    gh, gw = h // BH, w // BW
    _compile(
        topo,
        lambda x, prev, bmax, mask: ek.edge_stream_pallas(
            x, prev, bmax, mask, block_h=BH, block_w=BW, out_nms=True
        ),
        ((n, h, w), jnp.uint8),
        ((n, h, w), jnp.float32),
        ((n, gh, gw), jnp.float32),
        ((n, gh, gw), jnp.int32),
    )


@pytest.mark.parametrize("edges", [False, True], ids=["magnitude", "edges"])
def test_serving_step_compiles_for_v5e(topo, edges):
    """The whole jitted step ``launch/serve.py`` runs per sobel-hd request
    (4 f32 frames of 2048^2): the kernel plus the XLA stages around it —
    peaks, normalization and, with ``edges``, the hysteresis loop."""
    from repro.api import edge_detect
    from repro.configs import get_config
    from repro.launch.serve import image_edge_config

    cfg = get_config("sobel-hd").replace(sobel_backend="pallas-tpu")
    ecfg = image_edge_config(cfg, edges=edges)
    _compile(topo, lambda f: edge_detect(f, ecfg), (HD, jnp.float32))


def test_u16_inspection_step_compiles_for_v5e(topo):
    """The ``inspect-12mp`` request (one 3000x4096 12-bit frame in a u16
    container), the fused ``with_max`` kernel in the lane ``auto`` picks:
    the frame reaches the kernel's custom call as ``u16``, with no f32
    copy of it made before the kernel."""
    import re

    from repro.api import edge_detect
    from repro.configs import get_config
    from repro.launch.serve import image_edge_config

    cfg = get_config("inspect-12mp").replace(sobel_backend="pallas-tpu")
    ecfg = image_edge_config(cfg)
    shape = (1, cfg.image_h, cfg.image_w)
    text = _compile(topo, lambda f: edge_detect(f, ecfg), (shape, jnp.uint16))
    plane = "[{},{},{}]".format(*shape)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and re.search(r"%edge_u16_(int32|f32)", calls[0])
    assert f"u16{plane}" in calls[0]
    assert not [ln for ln in text.splitlines()
                if " convert(" in ln and f"f32{plane}" in ln]


@pytest.mark.parametrize("backend", ["pallas-tpu", "xla"])
def test_stream_engine_step_compiles_for_v5e(topo, backend):
    """The stream engine's jitted programs (``serve/streams.py``) on a warm
    state of 2 u8 streams at 2048^2: the per-tile delta test and the masked
    step, on the kernel backend and on its XLA fallback. These XLA stages
    are where the chip's compiler, not Mosaic, can refuse (e.g. a
    ``reduce_window`` that overflows scoped VMEM)."""
    from repro.api import EdgeConfig, StreamState
    from repro.kernels import dispatch

    n, h, w = 2, 2048, 2048
    cfg = EdgeConfig(backend=backend, nms=True, hysteresis=True,
                     with_max=True, block_h=BH, block_w=BW)
    one = SingleDeviceSharding(topo.devices[0])
    cold = StreamState.init(n, h, w, cfg, dtype=jnp.uint8)
    leaves, (block, _) = cold.tree_flatten()
    warm = StreamState(*(
        None if a is None else jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=one)
        for a in leaves
    ), block, True)
    frames = jax.ShapeDtypeStruct((n, h, w), jnp.uint8, sharding=one)
    mask = jax.ShapeDtypeStruct((n, h // BH, w // BW), jnp.bool_,
                                sharding=one)
    jax.jit(dispatch.stream_delta).lower(frames, warm, cfg).compile()
    text = jax.jit(dispatch.edge_stream, static_argnames=("layout",)).lower(
        frames, cfg, warm, layout="NHW", changed=mask
    ).compile().as_text()
    assert ("tpu_custom_call" in text) == (backend == "pallas-tpu")
