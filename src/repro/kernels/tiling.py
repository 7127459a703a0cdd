"""Zero-copy tile geometry for the fused Pallas Sobel kernels.

PR 1 tiled a *pre-padded* copy of the image: ``ops.sobel`` materialized
``jnp.pad(x, r)`` (boundary) plus a second pad up to block multiples, and the
kernel stitched four non-overlapping BlockSpec views back into one halo'd
tile. Those two pads and the final un-pad slice were three whole-image HBM
round-trips the kernel never saw.

This module removes them. Each grid step reads one *clamped window* of the
raw, unpadded ``(N, H, W)`` plane via ``pl.Element`` block dims — the index
map returns element offsets, so the input windows of consecutive grid steps
may overlap and are shifted (clamped) at the image edges so every read stays
in bounds. Mosaic only accepts a window whose origin is provably a multiple
of the ``(8, 128)`` tile of the plane's last two dims, so the origin is the
stencil's first row/column rounded *down* to that tile, and the window is
one tile longer than the stencil needs to still cover it:

    row0 = clip((k * block_h - r) & -8, 0, H_pad - tile_h)

The same geometry runs on every backend — the Pallas interpreter reads
exactly the windows the chip does, so the CPU bit-exactness tests cover the
offsets the hardware uses.

Boundary handling moves *inside* the kernel: for each row/column of the
halo'd tile the kernel computes the source coordinate under the padding rule
(``reflect`` via the mirror-periodic map, ``edge``/``zero`` via clamping),
translates it into the clamped window, and applies it as a one-hot
permutation matmul (``P @ x @ Q^T``). A one-hot f32 matmul at
``Precision.HIGHEST`` is an exact selection — every product is ``0 * v``
or ``1 * v``, with no bf16 rounding of ``v`` on the MXU — so the fused kernels
stay bit-exact against ``repro.core.sobel``'s ``jnp.pad`` semantics, while
the permutation runs on the MXU on hardware. ``zero`` padding additionally
masks the out-of-range rows/columns to 0.

Ragged images: the grid is ``ceil(H / block_h)`` x ``ceil(W / block_w)``,
out-of-range output rows/cols of the last blocks are dropped by Pallas's
masked stores, and ``valid_mask`` excludes them from in-kernel reductions
(the per-block max used for fused normalization). An axis that needs more
than one window but is not a multiple of its tile is read as if padded up to
one (:func:`padded_shape`): the window spec declares the padding
(``pl.Element(t, (0, pad))``), so no copy is made, and the kernel zeroes the
undefined padded cells before its selection matmul ever sees them.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "PAD_MODES",
    "ALIGN",
    "window_shape",
    "padded_shape",
    "window_spec",
    "window_origin",
    "reflect_index",
    "boundary_index",
    "extend_tile",
    "valid_mask",
    "as_f32",
    "luma",
    "halo_amplification",
    "window_amplification",
    "tile_vmem_bytes",
]

PAD_MODES = ("reflect", "edge", "zero")


def window_radius(radius: int, nms: bool = False) -> int:
    """Input-window reach of a fused kernel step, in pixels.

    THE single source of truth for halo sizing: the operator stencil needs
    ``radius``, and NMS compares the magnitude against a 1-px neighborhood
    on top of it. The Pallas window spec (``repro.kernels.edge``), the
    streaming delta-dilation (``repro.kernels.dispatch``), and the sharded
    halo exchange (``repro.sharding.halo.exchange_radius``) all derive
    their reach from this function, and the static analyzer
    (``repro.analysis`` rule HALO001) checks that every traced kernel
    window covers it.
    """
    return radius + (1 if nms else 0)


# Mosaic's (sublane, lane) tile for the last two dims of a 32-bit plane: a
# window's origin and extent along each must be multiples of it, unless the
# window spans the whole axis.
ALIGN = (8, 128)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _axis_window(n: int, b: int, r: int, a: int) -> Tuple[int, int]:
    """(window length, padded axis length) along one axis.

    An aligned origin sits up to ``a - 1`` before the stencil's first
    element, so the window is ``b + 2r + a - 1`` rounded up to the tile. An
    axis that one window covers is read whole (a block dim equal to the
    array dim is always legal); otherwise it is padded to the tile so the
    last window can end on it.
    """
    t = _round_up(b + 2 * r + a - 1, a)
    if t >= n:
        return n, n
    return t, _round_up(n, a)


def window_shape(h: int, w: int, block_h: int, block_w: int, r: int
                 ) -> Tuple[int, int]:
    """(tile_h, tile_w) of the clamped input window for one output block."""
    return (_axis_window(h, block_h, r, ALIGN[0])[0],
            _axis_window(w, block_w, r, ALIGN[1])[0])


def padded_shape(h: int, w: int, block_h: int, block_w: int, r: int
                 ) -> Tuple[int, int]:
    """(H_pad, W_pad) of the plane the windows are read from (>= (h, w))."""
    return (_axis_window(h, block_h, r, ALIGN[0])[1],
            _axis_window(w, block_w, r, ALIGN[1])[1])


def _axis_origin(idx, n_pad: int, b: int, r: int, t: int, a: int):
    """Clamped, ``a``-aligned window origin of block ``idx`` along one axis
    (``a`` is a power of two, so ``& -a`` rounds down for negative values
    too)."""
    return jnp.clip((idx * b - r) & -a, 0, n_pad - t)


def window_origin(k, j, hp: int, wp: int, block_h: int, block_w: int, r: int,
                  tile_h: int, tile_w: int):
    """Clamped, tile-aligned (row0, col0) of grid step (k, j)'s input window
    in the padded ``(hp, wp)`` plane.

    Used both by the BlockSpec index map and inside the kernel body (it is a
    pure function of the static geometry and the grid indices).
    """
    row0 = _axis_origin(k, hp, block_h, r, tile_h, ALIGN[0])
    col0 = _axis_origin(j, wp, block_w, r, tile_w, ALIGN[1])
    return pl.multiple_of(row0, ALIGN[0]), pl.multiple_of(col0, ALIGN[1])


def window_spec(
    h: int,
    w: int,
    block_h: int,
    block_w: int,
    r: int,
    *,
    channels: "int | None" = None,
) -> pl.BlockSpec:
    """Element-indexed BlockSpec reading the clamped window of an
    ``(N, H_pad, W_pad)`` plane (``(N, C, H_pad, W_pad)`` with
    ``channels``, the planar RGB layout).

    The index map returns *element* offsets (every block dim is
    ``pl.Element`` — Mosaic takes all or none), which is what lets
    consecutive grid steps read overlapping windows — no halo staging copy.
    ``h``/``w`` are the true frame dims; the trailing padding up to
    :func:`padded_shape` is declared on the window, not materialized.
    """
    th, tw = window_shape(h, w, block_h, block_w, r)
    hp, wp = padded_shape(h, w, block_h, block_w, r)

    def _origin(i, k, j):
        row0, col0 = window_origin(k, j, hp, wp, block_h, block_w, r, th, tw)
        return (i, row0, col0) if channels is None else (i, 0, row0, col0)

    lead = (1,) if channels is None else (1, channels)
    return pl.BlockSpec(
        tuple(pl.Element(d) for d in lead)
        + (pl.Element(th, (0, hp - h)), pl.Element(tw, (0, wp - w))),
        _origin,
    )


# ---------------------------------------------------------------------------
# In-kernel boundary handling
# ---------------------------------------------------------------------------

def reflect_index(g: jnp.ndarray, n: int) -> jnp.ndarray:
    """numpy/jnp ``mode='reflect'`` source index for any overhang.

    The padded sequence is mirror-periodic with period ``2(n - 1)``; a
    single-pixel axis reflects to itself.
    """
    if n == 1:
        return jnp.zeros_like(g)
    period = 2 * (n - 1)
    m = jnp.mod(g, period)          # non-negative for negative g too
    return jnp.where(m < n, m, period - m)


def boundary_index(g: jnp.ndarray, n: int, padding: str) -> jnp.ndarray:
    """Source coordinate in [0, n) for requested coordinate ``g`` under the
    padding rule. ``zero`` clamps like ``edge`` — the caller masks the
    out-of-range rows/cols to 0 afterwards (see :func:`extend_tile`)."""
    if padding == "reflect":
        return jnp.clip(reflect_index(g, n), 0, n - 1)
    if padding in ("edge", "zero"):
        return jnp.clip(g, 0, n - 1)
    raise ValueError(f"unknown padding {padding!r}; expected one of {PAD_MODES}")


def _onehot_f32(g0, n_sel: int, n_win: int, n: int, origin, padding: str,
                *, transpose: bool = False) -> jnp.ndarray:
    """``(n_sel, n_win)`` one-hot selection matrix (``(n_win, n_sel)`` when
    ``transpose``): entry (p, c) is 1 where requested global coordinate
    ``g0 + p``, boundary-mapped into the image, sits at window position c.
    Built from 2-D iotas — Mosaic cannot reshape 1-D vectors."""
    shape = (n_win, n_sel) if transpose else (n_sel, n_win)
    sel_axis = 1 if transpose else 0
    g = g0 + jax.lax.broadcasted_iota(jnp.int32, shape, sel_axis)
    src = boundary_index(g, n, padding) - origin
    win = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - sel_axis)
    return (src == win).astype(jnp.float32)


def extend_tile(
    x: jnp.ndarray,
    k,
    j,
    *,
    h: int,
    w: int,
    block_h: int,
    block_w: int,
    r: int,
    padding: str = "reflect",
) -> jnp.ndarray:
    """Halo'd ``(block_h + 2r, block_w + 2r)`` tile for grid step (k, j),
    built from the clamped in-bounds window ``x`` (shape ``(tile_h, tile_w)``,
    already grayscale, in the kernel's compute dtype — f32 historically,
    i16/i32 on the exact integer lane).

    Interior tiles — every requested coordinate inside the image, the
    overwhelming majority on large frames — take a static-slice fast path:
    the stencil tile sits at the fixed offset ``(-r) mod`` tile inside its
    aligned window whenever the block dims are tile multiples. Tiles whose
    window was clamped (or whose block origin lands elsewhere in the tile)
    fall through to the general path. Boundary/ragged tiles run it too:
    two one-hot selection matmuls (exact; MXU-friendly) pick each requested
    global coordinate after boundary-mapping it into the image and
    translating it into the window — integer tiles round-trip through f32
    for the matmul, exact because every selected value is an integer in
    [-2^24, 2^24] (the ladder bound) and every product is ``0 * v`` or
    ``1 * v``. Requested coordinates that fall entirely outside the window
    only occur for output rows/cols past the ragged image edge — their
    one-hot rows are all-zero, producing 0s that Pallas's masked output
    store then drops.
    """
    th, tw = x.shape
    ext_h, ext_w = block_h + 2 * r, block_w + 2 * r
    hp, wp = padded_shape(h, w, block_h, block_w, r)
    row0, col0 = window_origin(k, j, hp, wp, block_h, block_w, r, th, tw)
    r0, c0 = k * block_h - r, j * block_w - r  # stencil tile's global origin

    def general(x):
        if (hp, wp) != (h, w):
            # cells past the frame edge are undefined (declared padding);
            # the one-hot matmul multiplies them by 0, so they must be finite
            inside = (
                (jax.lax.broadcasted_iota(jnp.int32, (th, tw), 0) < h - row0)
                & (jax.lax.broadcasted_iota(jnp.int32, (th, tw), 1) < w - col0)
            )
            x = jnp.where(inside, x, jnp.zeros((), x.dtype))
        p = _onehot_f32(r0, ext_h, th, h, row0, padding)
        q_t = _onehot_f32(c0, ext_w, tw, w, col0, padding, transpose=True)
        # HIGHEST: the MXU's default f32 contraction rounds the operands to
        # bf16, which keeps integers up to 256 but not fractional pixels
        select = functools.partial(jax.lax.dot,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
        y = select(p, select(x.astype(jnp.float32), q_t))
        if padding == "zero":
            gr = r0 + jax.lax.broadcasted_iota(jnp.int32, (ext_h, ext_w), 0)
            gc = c0 + jax.lax.broadcasted_iota(jnp.int32, (ext_h, ext_w), 1)
            inside = (gr >= 0) & (gr < h) & (gc >= 0) & (gc < w)
            y = jnp.where(inside, y, jnp.float32(0.0))
        return y.astype(x.dtype)

    # Where an unclamped aligned window puts the stencil's first row/col.
    off_h, off_w = (-r) % ALIGN[0], (-r) % ALIGN[1]
    if off_h + ext_h > th or off_w + ext_w > tw:
        # window too small for the offset slice: every tile is general
        return general(x)

    def interior(x):
        # a static slice — Mosaic cannot lower dynamic_slice on values
        return jax.lax.slice(x, (off_h, off_w), (off_h + ext_h, off_w + ext_w))

    is_interior = (
        (r0 >= 0)
        & (r0 + ext_h <= h)
        & (c0 >= 0)
        & (c0 + ext_w <= w)
        # the fast slice is only right where the stencil really starts at
        # the static offset: clamped windows and block origins elsewhere in
        # the tile take the general path
        & (row0 + off_h == r0)
        & (col0 + off_w == c0)
    )
    return jax.lax.cond(is_interior, interior, general, x)


def valid_mask(k, j, h: int, w: int, block_h: int, block_w: int) -> jnp.ndarray:
    """(block_h, block_w) bool mask of output pixels inside the image —
    False only in the ragged overhang of the last row/column blocks."""
    shape = (block_h, block_w)
    rv = k * block_h + jax.lax.broadcasted_iota(jnp.int32, shape, 0) < h
    cv = j * block_w + jax.lax.broadcasted_iota(jnp.int32, shape, 1) < w
    return rv & cv


# BT.601 luma weights (OpenCV cvtColor convention) — keep in sync with
# repro.core.pipeline.rgb_to_gray.
LUMA_WEIGHTS = (0.299, 0.587, 0.114)


def as_f32(x: jnp.ndarray) -> jnp.ndarray:
    """Exact cast to f32. Mosaic has no direct cast from narrow integers
    (u8/i8/i16) to f32, so those widen through i32 first."""
    if jnp.issubdtype(x.dtype, jnp.integer) and x.dtype.itemsize < 4:
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def luma(rgb: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """RGB -> f32 grayscale along the size-3 ``axis`` (``-1`` for
    interleaved ``(..., 3)`` pixels, ``0`` for the kernels' planar
    ``(3, h, w)`` windows), identical rounding to
    ``repro.core.pipeline.rgb_to_gray``.

    Each product is passed through ``maximum(w * c, -FLT_MAX)`` — an exact
    identity for every finite value that the XLA algebraic simplifier
    cannot fold — so XLA cannot contract the multiplies into FMAs. Without
    it, the jit-fused XLA pipeline and the Pallas kernel round a ~0.1%
    fraction of pixels differently (1 ulp), breaking the repo's
    bit-exactness contract (same trick as ``repro.core.sobel._tap``).
    """
    from repro.core.sobel import _F32_LOWEST

    x = as_f32(rgb)
    c = [jax.lax.index_in_dim(x, i, axis, keepdims=False) for i in range(3)]
    lo = jnp.float32(_F32_LOWEST)
    return (
        jnp.maximum(LUMA_WEIGHTS[0] * c[0], lo)
        + jnp.maximum(LUMA_WEIGHTS[1] * c[1], lo)
    ) + jnp.maximum(LUMA_WEIGHTS[2] * c[2], lo)


# ---------------------------------------------------------------------------
# Cost model (used by the tuner and the Fig. 6 sweep)
# ---------------------------------------------------------------------------

def halo_amplification(block_h: int, block_w: int, r: int) -> float:
    """Fraction of extra HBM reads vs a halo-free ideal (unaligned window)."""
    halo = 2 * r
    return (1.0 + halo / block_h) * (1.0 + halo / block_w) - 1.0


def window_amplification(
    h: int, w: int, block_h: int, block_w: int, r: int
) -> float:
    """Like :func:`halo_amplification` but for the actual (aligned, clamped)
    window a given image would use."""
    th, tw = window_shape(h, w, block_h, block_w, r)
    return (th * tw) / float(min(block_h, h) * min(block_w, w)) - 1.0


def tile_vmem_bytes(
    block_h: int,
    block_w: int,
    r: int,
    n_hpass: int = 5,
    channels: "int | None" = None,
) -> int:
    """Rough per-grid-step VMEM working set (f32): the aligned input window,
    the halo'd tile plus its two one-hot selection matrices, ``n_hpass``
    horizontal-pass intermediates, and the output tile."""
    eh, ew = block_h + 2 * r, block_w + 2 * r
    th = _round_up(eh + ALIGN[0] - 1, ALIGN[0])
    tw = _round_up(ew + ALIGN[1] - 1, ALIGN[1])
    window = th * tw * (channels or 1)
    onehots = eh * th + ew * tw
    tile = eh * ew
    inter = n_hpass * eh * block_w
    out = block_h * block_w
    return 4 * (window + onehots + tile + inter + out)
