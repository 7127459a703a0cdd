"""2-D (row x column) tiled Pallas kernels vs ``repro.core.sobel``.

These tests pin the acceptance bar for the tiling refactor: the fused kernel
and the dispatch layer must be *bit-exact* against the pure-XLA reference for
every variant, on sizes that are not multiples of either block dimension.
No optional deps (runs without hypothesis).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import EdgeConfig, edge_detect as api_edge_detect
from repro.core.sobel import sobel as core_sobel
from repro.kernels import tiling
from repro.kernels.edge import default_block_shape, edge_pallas, kernel_dtype


def _img(rng, shape, dtype=np.float32):
    return rng.integers(0, 256, size=shape).astype(dtype)


def pallas_sobel(img, *, size=5, directions=0, variant="v2", padding="reflect",
                 block_h=None, block_w=None, interpret=True, **kw):
    """Raw-kernel magnitude with the historical ``ops.sobel`` defaults."""
    x = kernel_dtype(img)
    batch = x.shape[:-2]
    h, w = x.shape[-2], x.shape[-1]
    dbh, dbw = default_block_shape(h, w, size)
    out = edge_pallas(
        x.reshape((-1, h, w)), operator=f"sobel{size}", variant=variant,
        directions=directions, padding=padding, block_h=block_h or dbh,
        block_w=block_w or dbw, interpret=interpret, **kw,
    )
    return out.reshape(batch + (h, w))


def dispatch_sobel(img, *, backend=None, variant="v2", block_h=None, block_w=None):
    cfg = EdgeConfig(variant=variant, normalize=False, backend=backend,
                     block_h=block_h, block_w=block_w)
    layout = "N" * max(0, img.ndim - 2) + "HW"
    return api_edge_detect(img, cfg, layout=layout).magnitude


@pytest.mark.parametrize("variant", ["direct", "separable", "v1", "v2"])
@pytest.mark.parametrize(
    "shape,block",
    [((1, 57, 83), (8, 16)), ((2, 96, 73), (32, 32)), ((1, 64, 128), (16, 64)),
     # chip-sized blocks: tile-aligned windows, declared row padding
     # (237 is not a multiple of 8), and a 1080p frame of 17x8 blocks
     ((1, 237, 413), (64, 256)), ((1, 1080, 1920), (64, 256))],
)
def test_2d_tiling_bit_exact(variant, shape, block, rng):
    img = jnp.asarray(_img(rng, shape))
    out = np.asarray(
        pallas_sobel(img, variant=variant, block_h=block[0], block_w=block[1], interpret=True)
    )
    ref = np.asarray(core_sobel(img, variant=variant))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("variant", ["direct", "separable", "v1", "v2"])
def test_dispatch_bit_exact_non_block_multiple(variant, rng):
    """Acceptance: dispatch == core, bit-exact, on 237x413 (neither dim a
    block multiple)."""
    img = jnp.asarray(_img(rng, (1, 237, 413)))
    out = np.asarray(
        dispatch_sobel(img, variant=variant, backend="pallas-interpret",
                       block_h=64, block_w=128)
    )
    ref = np.asarray(core_sobel(img, variant=variant))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("padding", ["reflect", "edge", "zero"])
def test_2d_tiling_paddings(padding, rng):
    img = jnp.asarray(_img(rng, (1, 41, 77)))
    out = np.asarray(
        pallas_sobel(img, padding=padding, block_h=8, block_w=16, interpret=True)
    )
    ref = np.asarray(core_sobel(img, padding=padding))
    np.testing.assert_array_equal(out, ref)


def test_2d_block_shape_invariance(rng):
    """Output must not depend on the tile geometry at all."""
    img = jnp.asarray(_img(rng, (1, 128, 96)))
    outs = [
        np.asarray(pallas_sobel(img, variant="v2", block_h=bh, block_w=bw, interpret=True))
        for bh in (8, 32, 128)
        for bw in (8, 32, 96)
    ]
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


@pytest.mark.parametrize("directions", [2, 4])
@pytest.mark.parametrize("variant", ["direct", "separable"])
def test_2d_tiling_3x3(directions, variant, rng):
    img = jnp.asarray(_img(rng, (2, 61, 45)))
    out = np.asarray(
        pallas_sobel(img, size=3, directions=directions, variant=variant,
                     block_h=16, block_w=16, interpret=True)
    )
    ref = np.asarray(core_sobel(img, size=3, directions=directions, variant=variant))
    np.testing.assert_array_equal(out, ref)


def test_2d_tiling_uint8_input(rng):
    img = _img(rng, (1, 50, 70), np.uint8)
    out = np.asarray(pallas_sobel(jnp.asarray(img), block_h=8, block_w=24, interpret=True))
    ref = np.asarray(core_sobel(jnp.asarray(img).astype(jnp.float32)))
    np.testing.assert_array_equal(out, ref)


def test_components_output_2d(rng):
    from repro.kernels.ref import sobel_components_ref

    img = jnp.asarray(_img(rng, (1, 32, 48)))
    comps = edge_pallas(
        img, operator="sobel5", variant="v2", out_components=True,
        block_h=16, block_w=16, interpret=True,
    )
    assert comps.shape == (1, 4, 32, 48)
    refs = sobel_components_ref(img)
    for i, ref in enumerate(refs):
        np.testing.assert_allclose(
            np.asarray(comps[:, i]), np.asarray(ref), rtol=1e-6, atol=1e-3
        )


def test_edge_detect_backend_parity(rng):
    """Pipeline wiring: edge_detect(backend=...) must agree across backends."""
    img = jnp.asarray(_img(rng, (2, 37, 53)))
    base = EdgeConfig()
    x = np.asarray(api_edge_detect(img, base.replace(backend="xla")).magnitude)
    p = np.asarray(api_edge_detect(
        img, base.replace(backend="pallas-interpret", block_h=8, block_w=16)
    ).magnitude)
    np.testing.assert_array_equal(p, x)


# ---------------------------------------------------------------------------
# Tile geometry unit tests
# ---------------------------------------------------------------------------

def test_window_shape_geometry():
    # One geometry on every backend: the stencil window rounded up to the
    # Mosaic (8, 128) tile plus one tile of slack for the aligned-down
    # origin; clamped to the whole axis when one window covers it.
    assert tiling.window_shape(512, 640, 64, 128, 2) == (80, 384)
    assert tiling.window_shape(5, 7, 64, 128, 2) == (5, 7)
    assert tiling.window_shape(2048, 2048, 64, 256, 3) == (80, 512)
    # Only a multi-window axis that is not a tile multiple is padded.
    assert tiling.padded_shape(237, 413, 64, 256, 2) == (240, 413)
    assert tiling.padded_shape(1080, 1920, 64, 256, 2) == (1080, 1920)
    # Origins are tile-aligned, cover the stencil, and clamp in bounds.
    hp, wp, bh, bw, r = 240, 1920, 64, 256, 2
    th, tw = tiling.window_shape(237, wp, bh, bw, r)
    for k in range(4):
        for j in range(8):
            row0, col0 = (int(v) for v in tiling.window_origin(
                k, j, hp, wp, bh, bw, r, th, tw))
            assert row0 % 8 == 0 and col0 % 128 == 0
            assert 0 <= row0 <= hp - th and 0 <= col0 <= wp - tw
            assert row0 <= max(k * bh - r, 0)
            assert row0 + th >= min(k * bh + bh + r, 237)
            assert col0 <= max(j * bw - r, 0)
            assert col0 + tw >= min(j * bw + bw + r, wp)


@pytest.mark.parametrize(
    "k,j,fast",
    [(1, 1, True), (30, 6, True), (0, 3, False), (5, 0, False),
     (31, 3, False), (5, 7, False)],
)
def test_interior_tiles_take_the_static_slice(k, j, fast):
    """On sobel-hd's 2048^2 / 64x256 geometry, tiles whose stencil lies
    inside the frame and whose aligned window is not clamped take the
    static-slice fast path; edge tiles take the selection matmul. The window
    is NaN outside the stencil: the matmul spreads it (0 * NaN), the slice
    never reads it."""
    h = w = 2048
    bh, bw, r = 64, 256, 2
    th, tw = tiling.window_shape(h, w, bh, bw, r)
    row0, col0 = (int(v) for v in tiling.window_origin(
        k, j, h, w, bh, bw, r, th, tw))
    win = np.full((th, tw), np.nan, np.float32)
    win[max(k * bh - r, 0) - row0:min(k * bh + bh + r, h) - row0,
        max(j * bw - r, 0) - col0:min(j * bw + bw + r, w) - col0] = 1.0
    y = tiling.extend_tile(jnp.asarray(win), k, j, h=h, w=w, block_h=bh,
                           block_w=bw, r=r)
    assert bool(np.isfinite(np.asarray(y)).all()) == fast


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, nested jaxprs (kernel bodies, cond
    branches) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_selection_matmul_runs_at_highest_precision():
    """The boundary tiles' one-hot selection is exact only at HIGHEST: the
    MXU's default f32 contraction rounds its operands to bf16, which keeps
    integer pixels up to 256 but not fractional ones (RGB luma, f32
    frames)."""
    import jax

    jaxpr = jax.make_jaxpr(
        lambda a: edge_pallas(a, block_h=16, block_w=128, interpret=True)
    )(jnp.zeros((1, 40, 300), jnp.float32))
    dots = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "dot_general"]
    assert dots
    for e in dots:
        assert e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2


def test_boundary_index_matches_numpy_pad():
    # reflect/edge source indices must match np.pad semantics for any
    # overhang (incl. overhang wider than the axis).
    for n in (1, 2, 3, 7):
        g = np.arange(-4, n + 4)
        padded_order = np.pad(np.arange(n), (4, 4), mode="reflect")
        got = np.asarray(tiling.boundary_index(jnp.asarray(g), n, "reflect"))
        np.testing.assert_array_equal(got, padded_order)
        edge = np.pad(np.arange(n), (4, 4), mode="edge")
        got_e = np.asarray(tiling.boundary_index(jnp.asarray(g), n, "edge"))
        np.testing.assert_array_equal(got_e, edge)
    with pytest.raises(ValueError):
        tiling.boundary_index(jnp.arange(3), 8, "wrap")


def test_halo_amplification_monotone():
    # Bigger tiles -> less re-read; 2-D formula reduces to the seed's 4/bh
    # row-strip overhead when bw is the full (unsplit) width.
    assert tiling.halo_amplification(8, 8, 2) > tiling.halo_amplification(64, 64, 2)
    big_w = tiling.halo_amplification(64, 10**9, 2)
    assert abs(big_w - 4 / 64) < 1e-6


def test_tile_vmem_independent_of_width():
    # The point of 2-D tiling: VMEM is O(bh * bw), not O(bh * W). A 64x256
    # tile on an 8K-wide frame is ~32x leaner than the seed's full-width
    # row strip (= a bw=8192 tile).
    tile = tiling.tile_vmem_bytes(64, 256, 2)
    strip = tiling.tile_vmem_bytes(64, 8192, 2)
    assert tile * 16 < strip
