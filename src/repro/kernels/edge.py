"""Unified spec-driven Pallas megakernel for every registered edge operator.

One ``pallas_call`` wrapper serves the whole operator registry
(``repro.core.filters.OperatorSpec``): Sobel 3x3/5x5, Scharr, Prewitt, the
extended 7x7 Sobel, and any user-registered spec. The kernel body is the
*same* spec-driven variant ladder the pure-XLA path runs
(``repro.core.sobel.spec_components``) applied to a halo'd VMEM tile, so
cross-backend bit-exactness holds by construction for every operator.

GPU -> TPU mapping (see DESIGN.md §2) — unchanged from the PR-1/2
size-specialized kernels this module replaced:

  * paper's CUDA-block tile ownership + 2r overlap (§4.3.1)  ->  2-D tiled
    grid; step (k, j) owns a ``block_h x block_w`` output tile and reads a
    clamped, possibly overlapping, tile-aligned ``pl.Element`` window of
    the raw frame (``repro.kernels.tiling``); the halo radius r comes from
    the operator spec (r=1/2/3 for 3x3/5x5/7x7).
  * warp-shuffle register taps (§4.3.3)  ->  static strided slices of the
    VMEM-resident tile feeding the VPU.
  * explicit prefetch (§4.3.4)  ->  Pallas's automatic double buffering
    of the input windows: tile k+1's halo load overlaps tile k's compute.

``precision="int"`` is the exact low-precision lane: u8/u16 frames x
integer taps accumulated in the i16/i32 dtype ``repro.core.ladder``
proves, cast to f32 only at the magnitude/NMS boundary. Bit-identical to
the f32 lane by construction (both compute the same exact integers);
gated per-operator by the same budget DTYPE001 checks.

Both kernel bodies — the image kernel (:func:`edge_pallas`) and the
masked stream kernel (:func:`edge_stream_pallas`) — compute a tile through
one function, :func:`_tile_outputs`.

The kernel is a megakernel for the full edge-detection pipeline: raw u8
gray or RGB frame in (BT.601 luma per-tile in VMEM), in-kernel boundary
rule, multi-directional magnitude out — optionally per-direction gradient
components (``out_components``) and a per-block max (``with_max``) for
one-pass normalization. RGB frames are handed to the kernel planar,
``(N, 3, H, W)``, so the window's last two dims are the image's and align
to Mosaic's tile exactly as a grayscale window does.

``out_nms`` appends the direction-aware non-maximum suppression stage
(``repro.core.nms``) to the same pass: the halo window grows from
``radius`` to ``radius + 1`` (NMS needs a 1-px magnitude neighborhood, so
the existing clamped-window machinery extends rather than a new pipeline
stage), the component ladder runs on the ``(block + 2)``-sized inner tile,
and the kernel emits the *thin* magnitude — plus, on demand, the center
components (``out_components``), the un-thinned center magnitude
(``out_mag``, the peak source for the sharded path) and the per-block max
of the un-thinned magnitude (``with_max``, so normalization and the
hysteresis thresholds need no second whole-image read). The sector/
suppress math is imported from ``repro.core.nms`` verbatim — comparisons
and selects only — so the thin map is bit-identical to the XLA reference
(``core.nms.thin_map``) by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ladder
from repro.core.filters import OperatorSpec, get_operator, resolve_plan
from repro.core.nms import nms_sector, nms_thin
from repro.core.sobel import magnitude, plan_components, spec_components
from repro.kernels import tuning
from repro.kernels.tiling import (
    as_f32,
    extend_tile,
    luma,
    tile_vmem_bytes,
    valid_mask,
    window_radius,
    window_spec,
)

__all__ = [
    "edge_pallas",
    "edge_stream_pallas",
    "default_block_shape",
    "kernel_dtype",
    "kernel_name",
]

# Per-block maxima leave the kernel as one 128-lane row per grid step (the
# scalar broadcast across it): Mosaic lowers a vector store of a keepdims
# reduction, not a vector-to-scalar store into SMEM.
_LANES = 128


def _bmax_spec() -> pl.BlockSpec:
    return pl.BlockSpec((1, 1, 1, _LANES), lambda i, k, j: (i, k, 0, j))


def _bmax_shape(n: int, gh: int, gw: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((n, gh, 1, gw * _LANES), jnp.float32)


def _bmax_unpack(rows: jnp.ndarray) -> jnp.ndarray:
    """``(N, gh, 1, gw * 128)`` lane rows -> ``(N, gh, gw)`` block maxima
    (a max over 128 copies of one value: exact, and no slice)."""
    n, gh, _, lanes = rows.shape
    return jnp.max(rows.reshape(n, gh, lanes // _LANES, _LANES), axis=-1)


def _bmax_pack(bmax: jnp.ndarray) -> jnp.ndarray:
    """``(N, gh, gw)`` block maxima -> the kernel's lane-row layout."""
    n, gh, gw = bmax.shape
    rows = jnp.broadcast_to(bmax[..., None], (n, gh, gw, _LANES))
    return rows.reshape(n, gh, 1, gw * _LANES)


def _block_max(mag, k, j, *, h, w, bh, bw):
    """Masked per-block max of a ``(bh, bw)`` magnitude tile, as a
    ``(1, 128)`` row (the magnitude is >= 0, so masking to 0 is exact)."""
    masked = jnp.where(valid_mask(k, j, h, w, bh, bw), mag, jnp.float32(0.0))
    return jnp.broadcast_to(jnp.max(masked, keepdims=True), (1, _LANES))


def _planar(x: jnp.ndarray, rgb: bool) -> jnp.ndarray:
    """The kernels' input layout: ``(N, H, W)``, or RGB as ``(N, 3, H, W)``
    planes (interleaved channels would make the window's column dim the
    second-minor one, which Mosaic cannot align)."""
    return jnp.moveaxis(x, -1, 1) if rgb else x


def _to_compute(x: jnp.ndarray, acc_dtype) -> jnp.ndarray:
    """Gray window -> the kernel compute dtype (f32, or the integer lane's
    i16/i32, widening through i32 as Mosaic requires)."""
    if not acc_dtype:
        return as_f32(x)
    return x.astype(jnp.int32).astype(jnp.dtype(acc_dtype))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def default_block_shape(
    h: int,
    w: int,
    size: int = 5,
    *,
    channels: "int | None" = None,
    max_vmem_bytes: int = tuning.VMEM_BUDGET,
) -> tuple:
    """Conservative (block_h, block_w) when no tuned shape is available.

    Multiples of 8 match the f32 sublane tile; 256 lanes = 2 VPU lane tiles.
    Small images shrink the block instead of spilling into masked overhang,
    and the operator's halo (2r, from ``size``) is folded into a VMEM-fit
    bound: the halo'd working set of the tile must fit ``max_vmem_bytes``,
    shrinking the block if a large operator (or a small budget) demands it.
    """
    r = size // 2
    bh = min(64, _round_up(h, 8))
    bw = min(256, _round_up(w, 8))
    # Halo'd working set must fit; halve the larger dimension until it does
    # (floor 8x8 — below that the halo dominates and no block helps).
    while tile_vmem_bytes(bh, bw, r, channels=channels) > max_vmem_bytes and (
        bh > 8 or bw > 8
    ):
        if bw >= bh and bw > 8:
            bw = max(8, bw // 2)
        else:
            bh = max(8, bh // 2)
    return bh, bw


# Integer frame dtypes the kernels read raw from HBM and widen per block in
# VMEM, the integer lane's inputs: 8-bit video (4x less input traffic than
# f32) and 12/16-bit sensor frames in 16-bit containers (2x less).
RAW_DTYPES = ladder.INT_LANE_INPUTS


def kernel_dtype(x: jnp.ndarray) -> jnp.ndarray:
    """The repo-wide kernel dtype policy.

    ``uint8`` and ``uint16`` are kept as-is (the kernel casts per block in
    VMEM); every other integer/bool/float dtype is cast to float32 here
    (the kernels compute in f32 everywhere).
    """
    if x.dtype in RAW_DTYPES:
        return x
    return x.astype(jnp.float32)


def kernel_name(dtype, lane: str, *, stream: bool = False) -> str:
    """The ``pallas_call``'s name, as the device trace shows it: the input
    dtype the kernel reads and the lane it computes in (the integer lane's
    accumulation dtype, else f32), e.g. ``edge_u16_int32``."""
    dt = jnp.dtype(dtype)
    return f"edge_{'stream_' if stream else ''}{dt.kind}{dt.itemsize * 8}_{lane}"


# ---------------------------------------------------------------------------
# Kernel bodies — one tile function on the VMEM-resident halo'd tile
# ---------------------------------------------------------------------------

def _tile_outputs(
    x_raw, k, j, *,
    spec, variant, directions, bh, bw, h, w, padding, rgb, out_nms,
    out_components=False, out_mag=False, with_max=False, acc_dtype=None,
    plan=None,
):
    """The tile math of both kernel bodies: raw halo'd window -> outputs.

    Yields the tile's outputs in :func:`edge_pallas`'s order — primary,
    then what ``out_components``/``out_mag``/``with_max`` ask for — each as
    soon as it is computed, so a body that stores while it iterates keeps
    no finished output live while the next is computed.

    ``x_raw`` is the window as loaded (gray, or planar RGB reduced to luma
    here); a gray window is cast to the compute dtype (f32, or the integer
    lane's ``acc_dtype``). The gradient ladder runs in that dtype;
    components are cast to f32 before the magnitude/NMS stage either way,
    so both lanes yield bit-identical f32 outputs (``repro.core.ladder``
    proves every integer intermediate is f32-exact).

    ``plan`` (a multi-stage :class:`~repro.core.filters.StencilPlan`)
    chains the plan's single-plane pre-stages ahead of the gradient ladder
    on the same halo'd tile — the tile is extended by the *composed* linear
    reach and each stage consumes its own radius off the margin
    (``core.sobel.plan_components``, the same walk the XLA reference
    runs).
    """
    x = luma(x_raw, axis=0) if rgb else _to_compute(x_raw, acc_dtype)
    reach = plan.linear_reach if plan is not None else spec.radius

    def components(y, hh, ww):
        if plan is not None and plan.pre_stages:
            return plan_components(y, plan, hh, ww, variant, directions)
        return spec_components(y, spec, hh, ww, variant, directions)

    def comps_f32(comps):
        return tuple(as_f32(c) for c in comps)

    def block_max(mag):
        """Masked per-block max of the (un-thinned) center magnitude."""
        return _block_max(mag, k, j, h=h, w=w, bh=bh, bw=bw)

    if out_nms:
        # NMS needs a 1-px magnitude neighborhood: grow the halo to
        # reach + 1, run the stage chain on the (bh + 2, bw + 2) inner
        # tile, suppress down to the (bh, bw) output block (core.nms math,
        # shared with XLA).
        y = extend_tile(
            x, k, j, h=h, w=w, block_h=bh, block_w=bw, r=reach + 1,
            padding=padding,
        )
        comps_ext = comps_f32(components(y, bh + 2, bw + 2))
        mag_ext = magnitude(comps_ext)
        comps = tuple(
            jax.lax.slice(g, (1, 1), (1 + bh, 1 + bw)) for g in comps_ext
        )
        yield nms_thin(mag_ext, nms_sector(comps))
        if out_components:
            yield jnp.stack(comps, axis=0)  # (directions, bh, bw)
        mag = jax.lax.slice(mag_ext, (1, 1), (1 + bh, 1 + bw))
        if out_mag:
            yield mag
        if with_max:
            yield block_max(mag)
        return

    y = extend_tile(
        x, k, j, h=h, w=w, block_h=bh, block_w=bw, r=reach,
        padding=padding,
    )
    comps = comps_f32(components(y, bh, bw))
    if out_components:
        yield jnp.stack(comps, axis=0)     # (directions, bh, bw)
        if with_max:
            # Per-block maxima ride along with the components, so callers
            # needing components AND the peak pay no second whole-image
            # reduction read (dispatch's fused normalization fast path).
            yield block_max(magnitude(comps))
        return
    mag = magnitude(comps)
    yield mag
    if with_max:
        yield block_max(mag)


def _kernel(x_ref, *o_refs, **tile):
    k = pl.program_id(1)
    j = pl.program_id(2)
    outs = _tile_outputs(x_ref[0], k, j, **tile)
    for ref, out in zip(o_refs, outs, strict=True):
        # Every output block is the tile behind leading unit dims.
        ref[(0,) * (len(ref.shape) - out.ndim)] = out


def _stream_kernel(
    mask_ref, x_ref, prev_ref, prevmax_ref, o_ref, omax_ref, **tile
):
    """Masked-grid streaming body: per-tile recompute-or-splice.

    The delta dispatcher marks each tile changed/unchanged in an SMEM mask
    (``(N, gh, gw)`` int32, one flag per grid step). A changed tile runs
    :func:`_tile_outputs`, the math of :func:`_kernel`, for its primary map
    and block max; an unchanged tile splices the cached output tile and
    per-block max (both per-block maxima in the lane-row layout of
    ``_bmax_spec``) instead — one ``lax.cond`` per grid step, so Mosaic
    branches over the whole tile compute and the skipped tile costs only
    the (unavoidable) window DMA plus a VMEM copy. Splice == recompute
    bit-exactly because an unchanged input window reproduces identical
    arithmetic, inductively across frames.
    """
    k = pl.program_id(1)
    j = pl.program_id(2)
    changed = mask_ref[0, k, j] != 0

    def fresh(x_raw):
        return tuple(_tile_outputs(x_raw, k, j, with_max=True, **tile))

    def cached(_x_raw):
        return prev_ref[0], prevmax_ref[0, 0]

    out, bmax = jax.lax.cond(changed, fresh, cached, x_ref[0])
    o_ref[0] = out
    omax_ref[0, 0] = bmax


# ---------------------------------------------------------------------------
# pallas_call wrappers (operate on the raw, unpadded batch)
# ---------------------------------------------------------------------------

def _grid(x, *, rgb, block_h, block_w, reach, out_nms):
    """Grid and input-window geometry of both wrappers: ``(n, h, w, bh, bw,
    gh, gw, in_spec)``. NMS compares the magnitude against a 1-px
    neighborhood, so its input window carries one extra ring on top of the
    (composed) stencil halo ``reach``."""
    if rgb:
        n, h, w, _c = x.shape
    else:
        n, h, w = x.shape
    bh = block_h
    bw = block_w if block_w else w
    gh, gw = pl.cdiv(h, bh), pl.cdiv(w, bw)
    r_in = window_radius(reach, out_nms)
    in_spec = window_spec(h, w, bh, bw, r_in, channels=3 if rgb else None)
    return n, h, w, bh, bw, gh, gw, in_spec


@functools.partial(
    jax.jit,
    static_argnames=(
        "operator",
        "variant",
        "params",
        "directions",
        "padding",
        "block_h",
        "block_w",
        "rgb",
        "out_components",
        "out_nms",
        "out_mag",
        "with_max",
        "precision",
        "plan",
        "interpret",
    ),
)
def edge_pallas(
    x: jnp.ndarray,
    *,
    operator: str = "sobel5",
    variant: str = "v2",
    params: "SobelParams | None" = None,
    directions: int = 0,   # 0 = operator max
    padding: str = "reflect",
    block_h: int = 64,
    block_w: "int | None" = None,
    rgb: bool = False,
    out_components: bool = False,
    out_nms: bool = False,
    out_mag: bool = False,
    with_max: bool = False,
    precision: str = "f32",
    plan: "StencilPlan | str | None" = None,
    interpret: bool = False,
):
    """Fused megakernel on the raw batch — any registered operator, any (H, W).

    ``x``: ``(N, H, W)`` grayscale (u8, u16 or f32), or ``(N, H, W, 3)`` RGB when
    ``rgb`` (BT.601 luma applied per-tile in VMEM).

    Outputs, in order (a bare array when only one):

      * primary ``(N, H, W)`` float32 — the magnitude, or the NMS thin
        magnitude when ``out_nms``, or (without ``out_nms``) the
        ``(N, directions, H, W)`` component stack when ``out_components``.
      * ``out_components`` with ``out_nms``: the ``(N, directions, H, W)``
        center components alongside the thin map.
      * ``out_mag`` (``out_nms`` only): the un-thinned ``(N, H, W)``
        magnitude — the peak source for the sharded engine, which cannot
        use the kernel's block maxima (its local valid mask differs).
      * ``with_max``: a ``(N, gh, gw)`` per-block max (gh/gw = grid dims) of
        the un-thinned magnitude, for one-pass normalization — available in
        every mode, including alongside ``out_components``.

    ``variant``/``directions`` must be valid for the operator (resolve via
    the spec first; see ``repro.api`` / ``repro.kernels.dispatch``).

    ``precision="int"`` runs the exact integer lane (u8/u16 gray input only;
    raises with the first failing eligibility gate otherwise — see
    ``repro.core.ladder``); outputs stay f32 and bit-identical to the
    default lane.

    ``plan`` (a :class:`~repro.core.filters.StencilPlan` or registered
    plan name) fuses the whole multi-stage chain into this same single
    launch: the input window and halo grow to the plan's *composed* linear
    reach (``sum of stage radii``, +1 for NMS), the pre-stages run on
    shrinking in-tile extents, and the gradient/NMS tail is unchanged. A
    one-gradient-stage plan takes the historical single-operator path
    byte-identically. The plan's NMS stage must match ``out_nms`` (the
    dispatcher derives one from the other).
    """
    if out_mag and not out_nms:
        raise ValueError("out_mag only applies with out_nms (the magnitude "
                         "is already the primary output otherwise)")
    if precision not in ("f32", "int"):
        # "auto" is a dispatch-level policy (repro.kernels.dispatch
        # resolves it before reaching the kernel wrapper).
        raise ValueError(
            f"unknown precision {precision!r}; expected 'f32' or 'int'"
        )
    plan = resolve_plan(plan)
    if plan is not None:
        spec = plan.gradient
        if spec is None:
            raise ValueError(
                f"plan {plan.name!r} has no gradient stage; the edge kernel "
                "emits direction components"
            )
        if out_nms != plan.nms:
            raise ValueError(
                f"plan {plan.name!r} {'ends in' if plan.nms else 'has no'} "
                f"NMS stage but out_nms={out_nms}; the plan is the single "
                "source of truth — pass out_nms=plan.nms"
            )
        if plan.single_operator:
            plan = None  # historical single-operator path, byte-identical
    else:
        spec = get_operator(operator, params)
    variant = spec.resolve_variant(variant)
    directions = spec.resolve_directions(directions)
    acc_dtype = None
    if precision == "int":
        if plan is not None:
            ok, reason = ladder.plan_int_eligible(
                plan, rgb=rgb, input_dtype=x.dtype
            )
        else:
            ok, reason = ladder.int_lane_eligible(
                spec, rgb=rgb, input_dtype=x.dtype
            )
        if not ok:
            raise ValueError(f"precision='int' unavailable: {reason}")
        acc_dtype = (
            ladder.plan_accum_dtype(plan, input_dtype=x.dtype)
            if plan is not None
            else ladder.accum_dtype(spec, input_dtype=x.dtype)
        )
        if not interpret and acc_dtype == "int16":
            # Mosaic's 16-bit vector coverage is incomplete (e.g. no i16
            # neg); i32 holds every i16-bounded intermediate exactly, so
            # widening preserves bit-exactness. Interpret/XLA lanes keep
            # the narrow dtype the ladder licenses.
            acc_dtype = "int32"
    n, h, w, bh, bw, gh, gw, in_spec = _grid(
        x, rgb=rgb, block_h=block_h, block_w=block_w, out_nms=out_nms,
        reach=plan.linear_reach if plan is not None else spec.radius,
    )
    name = kernel_name(x.dtype, acc_dtype or "f32")
    x = _planar(x, rgb)

    plane = pl.BlockSpec((1, bh, bw), lambda i, k, j: (i, k, j))
    plane_shape = jax.ShapeDtypeStruct((n, h, w), jnp.float32)
    comps_spec = pl.BlockSpec(
        (1, directions, bh, bw), lambda i, k, j: (i, 0, k, j)
    )
    comps_shape = jax.ShapeDtypeStruct((n, directions, h, w), jnp.float32)

    if out_nms:
        out_specs, out_shape = [plane], [plane_shape]
        if out_components:
            out_specs.append(comps_spec)
            out_shape.append(comps_shape)
        if out_mag:
            out_specs.append(plane)
            out_shape.append(plane_shape)
    elif out_components:
        out_specs, out_shape = [comps_spec], [comps_shape]
    else:
        out_specs, out_shape = [plane], [plane_shape]
    if with_max:
        # Each grid step stores its block max as one 128-lane row.
        out_specs.append(_bmax_spec())
        out_shape.append(_bmax_shape(n, gh, gw))

    kernel = functools.partial(
        _kernel, spec=spec, variant=variant, directions=directions, bh=bh,
        bw=bw, h=h, w=w, padding=padding, rgb=rgb,
        out_components=out_components, out_nms=out_nms, out_mag=out_mag,
        with_max=with_max, acc_dtype=acc_dtype, plan=plan,
    )
    out = pl.pallas_call(
        kernel,
        grid=(n, gh, gw),
        in_specs=[in_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )(x)
    out = list(out)
    if with_max:
        out[-1] = _bmax_unpack(out[-1])
    if len(out) == 1:
        return out[0]
    return tuple(out)


@functools.partial(
    jax.jit,
    static_argnames=(
        "operator",
        "variant",
        "params",
        "directions",
        "padding",
        "block_h",
        "block_w",
        "rgb",
        "out_nms",
        "interpret",
    ),
)
def edge_stream_pallas(
    x: jnp.ndarray,
    prev_primary: jnp.ndarray,
    prev_bmax: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    operator: str = "sobel5",
    variant: str = "v2",
    params: "SobelParams | None" = None,
    directions: int = 0,
    padding: str = "reflect",
    block_h: int = 64,
    block_w: "int | None" = None,
    rgb: bool = False,
    out_nms: bool = False,
    interpret: bool = False,
):
    """Masked-grid megakernel for streaming frames: delta-skip tiles.

    ``x``: the current frames, ``(N, H, W[, 3])`` like :func:`edge_pallas`.
    ``prev_primary`` ``(N, H, W)`` f32 and ``prev_bmax`` ``(N, gh, gw)``
    f32 are the previous frame's primary map (thin magnitude when
    ``out_nms``, else magnitude) and per-block maxima; ``mask``
    ``(N, gh, gw)`` int32 flags the tiles whose input window changed. The
    kernel recomputes exactly the flagged tiles and splices the cached
    tile/maxima everywhere else, emitting ``(primary, bmax)`` for the
    whole frame — bit-identical to a full recompute, with the skipped
    tiles' arithmetic branched out (``lax.cond`` per grid step).

    The grid geometry (``block_h``/``block_w`` and hence ``gh``/``gw``)
    must match the one that produced ``prev_bmax``/``mask`` — the
    streaming dispatcher pins it in ``StreamState.block``.
    """
    spec: OperatorSpec = get_operator(operator, params)
    variant = spec.resolve_variant(variant)
    directions = spec.resolve_directions(directions)
    n, h, w, bh, bw, gh, gw, in_spec = _grid(
        x, rgb=rgb, block_h=block_h, block_w=block_w, reach=spec.radius,
        out_nms=out_nms,
    )
    if prev_bmax.shape != (n, gh, gw) or mask.shape != (n, gh, gw):
        raise ValueError(
            f"prev_bmax/mask {prev_bmax.shape}/{mask.shape} do not match the "
            f"({n}, {gh}, {gw}) tile grid of block ({bh}, {bw})"
        )
    mask_spec = pl.BlockSpec(
        (1, gh, gw), lambda i, k, j: (i, 0, 0), memory_space=pltpu.SMEM
    )
    plane = pl.BlockSpec((1, bh, bw), lambda i, k, j: (i, k, j))

    kernel = functools.partial(
        _stream_kernel, spec=spec, variant=variant, directions=directions,
        bh=bh, bw=bw, h=h, w=w, padding=padding, rgb=rgb, out_nms=out_nms,
    )
    primary, bmax = pl.pallas_call(
        kernel,
        grid=(n, gh, gw),
        in_specs=[mask_spec, in_spec, plane, _bmax_spec()],
        out_specs=[plane, _bmax_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, w), jnp.float32),
            _bmax_shape(n, gh, gw),
        ],
        interpret=interpret,
        name=kernel_name(x.dtype, "f32", stream=True),
    )(
        mask.astype(jnp.int32),
        _planar(x, rgb),
        prev_primary,
        _bmax_pack(prev_bmax),
    )
    return primary, _bmax_unpack(bmax)
