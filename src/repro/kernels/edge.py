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
    (``pipeline_depth=0``, the default), or — the paper's trick made
    explicit — a manual HBM->VMEM DMA ring (``pipeline_depth >= 2``): the
    input stays in ``pl.ANY`` memory and each grid step issues
    ``pltpu.make_async_copy`` for the window ``depth - 1`` steps ahead
    into a ``(depth, tile_h, tile_w)`` VMEM scratch ring, so tile k+1's
    halo load overlaps tile k's compute under our control (DESIGN.md §11).

Two orthogonal lanes thread through both pipelines:

  * ``precision="int"`` — the exact low-precision lane: u8 frames x
    integer taps accumulated in the i16/i32 dtype ``repro.core.ladder``
    proves, cast to f32 only at the magnitude/NMS boundary. Bit-identical
    to the f32 lane by construction (both compute the same exact
    integers); gated per-operator by the same budget DTYPE001 checks.
  * the registry's separable col (x) row factors exploited in-kernel: on
    the manual-DMA path the row passes F/S (and v2's D) spill into a
    dedicated VMEM scratch buffer (``spec_components``'s ``sink``) and
    the column passes read them back — deterministic VMEM residency for
    the reused factors, still one launch, values unchanged.

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
    pad_to_windows,
    padded_shape,
    tile_vmem_bytes,
    valid_mask,
    window_origin,
    window_radius,
    window_shape,
    window_spec,
)

__all__ = [
    "edge_pallas",
    "edge_stream_pallas",
    "default_block_shape",
    "kernel_dtype",
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


def kernel_dtype(x: jnp.ndarray) -> jnp.ndarray:
    """The repo-wide kernel dtype policy.

    ``uint8`` is kept as-is (4x less HBM input traffic; the kernel casts
    per-block in VMEM); every other integer/bool/float dtype is cast to
    float32 here (the kernels compute in f32 everywhere).
    """
    if x.dtype == jnp.uint8:
        return x
    return x.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Kernel body — pure math on the VMEM-resident halo'd tile
# ---------------------------------------------------------------------------

def _compute_dtype(acc_dtype):
    """Kernel compute dtype: the integer lane's proven i16/i32, else f32."""
    return jnp.dtype(acc_dtype) if acc_dtype else jnp.float32


def _emit_outputs(
    x, o_refs, k, j, *,
    spec, variant, directions, bh, bw, h, w, padding, out_components,
    out_nms, out_mag, with_max, sink=None, plan=None, stage_sink=None,
):
    """Shared tail of both fused kernel bodies: gray tile -> stored outputs.

    ``x`` is the grayscale window in the compute dtype (f32, or the integer
    lane's i16/i32). The gradient ladder runs in that dtype; components are
    cast to f32 before the magnitude/NMS stage either way, so both lanes
    store bit-identical f32 outputs (``repro.core.ladder`` proves every
    integer intermediate is f32-exact). ``sink`` forwards to
    ``spec_components`` (the manual-DMA path's row-pass VMEM spill).

    ``plan`` (a multi-stage :class:`~repro.core.filters.StencilPlan`)
    chains the plan's single-plane pre-stages ahead of the gradient ladder
    on the same halo'd tile — the tile is extended by the *composed* linear
    reach and each stage consumes its own radius off the margin
    (``core.sobel.plan_components``, the same walk the XLA reference
    runs). ``stage_sink`` spills the inter-stage planes (pipelined path).
    """
    reach = plan.linear_reach if plan is not None else spec.radius

    def components(y, hh, ww):
        if plan is not None and plan.pre_stages:
            return plan_components(y, plan, hh, ww, variant, directions,
                                   sink=sink, stage_sink=stage_sink)
        return spec_components(y, spec, hh, ww, variant, directions,
                               sink=sink)

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
        o = 0
        o_refs[o][0] = nms_thin(mag_ext, nms_sector(comps))
        if out_components:
            o += 1
            o_refs[o][0] = jnp.stack(comps, axis=0)  # (directions, bh, bw)
        mag = jax.lax.slice(mag_ext, (1, 1), (1 + bh, 1 + bw))
        if out_mag:
            o += 1
            o_refs[o][0] = mag
        if with_max:
            o_refs[o + 1][0, 0] = block_max(mag)
        return

    y = extend_tile(
        x, k, j, h=h, w=w, block_h=bh, block_w=bw, r=reach,
        padding=padding,
    )
    comps = comps_f32(components(y, bh, bw))
    if out_components:
        o_refs[0][0] = jnp.stack(comps, axis=0)     # (directions, bh, bw)
        if with_max:
            # Per-block maxima ride along with the components, so callers
            # needing components AND the peak pay no second whole-image
            # reduction read (dispatch's fused normalization fast path).
            o_refs[1][0, 0] = block_max(magnitude(comps))
        return
    mag = magnitude(comps)
    o_refs[0][0] = mag
    if with_max:
        o_refs[1][0, 0] = block_max(mag)


def _kernel(
    x_ref, *o_refs,
    spec, variant, directions, bh, bw, h, w, padding, rgb, out_components,
    out_nms, out_mag, with_max, acc_dtype=None, plan=None,
):
    k = pl.program_id(1)
    j = pl.program_id(2)
    x = luma(x_ref[0], axis=0) if rgb else _to_compute(x_ref[0], acc_dtype)
    _emit_outputs(
        x, o_refs, k, j,
        spec=spec, variant=variant, directions=directions, bh=bh, bw=bw,
        h=h, w=w, padding=padding, out_components=out_components,
        out_nms=out_nms, out_mag=out_mag, with_max=with_max, plan=plan,
    )


def _sink_slots(variant: str, directions: int) -> int:
    """Row-pass VMEM spill slots the manual-DMA path allocates.

    The separable ladder materializes the horizontal passes F and S
    (Eq. 5-7); RG-v2 adds the 2-tap difference D (Eq. 18-19). ``direct``
    has no row passes; 2-direction v2 never reaches D. Slot order is
    fixed: f=0, s=1, d=2.
    """
    if variant == "direct":
        return 0
    return 3 if (variant == "v2" and directions != 2) else 2


def _pipelined_kernel(
    x_hbm, *refs,
    spec, variant, directions, bh, bw, h, w, padding, rgb, out_components,
    out_nms, out_mag, with_max, acc_dtype, depth, th, tw, n_sink,
    plan=None, n_pre=0,
):
    """Manual double-buffered DMA body (``pipeline_depth >= 2``).

    The input stays in ``pl.ANY`` (HBM); a ``(depth, [3,] th, tw)``
    VMEM scratch ring plus a ``depth``-wide DMA semaphore array implement
    the paper's prefetch explicitly. Grid step j (j fastest, sequential
    under ``dimension_semantics=("arbitrary",)*3``):

      * j == 0 — refill: start copies for windows 0..depth-2 (new grid
        row; every prior copy was already waited, the ring is clean);
      * start the copy for window j+depth-1 (when it exists), keeping
        depth-1 loads in flight ahead of compute;
      * wait window j's copy, then compute from ring slot ``j % depth``.

    Each window's copy is started exactly once and waited exactly once;
    the window offsets are ``tiling.window_origin`` — the very function
    the automatic path's ``pl.Element`` index map uses — so both paths
    read byte-identical windows and the outputs are bit-exact across
    ``pipeline_depth`` settings. Analyzer rule PIPE001 checks the
    start/wait pairing and ring depth on the traced jaxpr.
    """
    n_scratch = 2 + (1 if n_sink else 0) + n_pre
    o_refs = refs[:len(refs) - n_scratch]
    scratch = refs[len(refs) - n_scratch:]
    buf, sem = scratch[0], scratch[1]
    rows = scratch[2] if n_sink else None
    pre_refs = scratch[2 + (1 if n_sink else 0):]

    i = pl.program_id(0)
    k = pl.program_id(1)
    j = pl.program_id(2)
    gw = pl.num_programs(2)
    reach = plan.linear_reach if plan is not None else spec.radius
    r_in = window_radius(reach, out_nms)

    hp, wp = padded_shape(h, w, bh, bw, r_in)

    def window_copy(j2, slot):
        row0, col0 = window_origin(k, j2, hp, wp, bh, bw, r_in, th, tw)
        win = (pl.ds(row0, th), pl.ds(col0, tw))
        src = x_hbm.at[(i, slice(None)) + win if rgb else (i,) + win]
        return pltpu.make_async_copy(src, buf.at[slot], sem.at[slot])

    @pl.when(j == 0)
    def _refill():
        for ahead in range(min(depth - 1, gw)):
            window_copy(ahead, ahead).start()

    @pl.when(j + depth - 1 < gw)
    def _prefetch():
        window_copy(j + depth - 1, jax.lax.rem(j + depth - 1, depth)).start()

    slot = jax.lax.rem(j, depth)
    window_copy(j, slot).wait()
    x_win = buf[slot]
    x = luma(x_win, axis=0) if rgb else _to_compute(x_win, acc_dtype)

    sink = None
    if n_sink:
        slots = {"f": 0, "s": 1, "d": 2}

        def sink(name, arr):
            rows[slots[name]] = arr
            return rows[slots[name]]

    stage_sink = None
    if n_pre:
        # Inter-stage VMEM spill: each pre-stage plane round-trips through
        # its dedicated scratch buffer (deterministic VMEM residency for
        # the chained stages; values unchanged, so still bit-exact).
        def stage_sink(idx, arr):
            pre_refs[idx][0] = arr
            return pre_refs[idx][0]

    _emit_outputs(
        x, o_refs, k, j,
        spec=spec, variant=variant, directions=directions, bh=bh, bw=bw,
        h=h, w=w, padding=padding, out_components=out_components,
        out_nms=out_nms, out_mag=out_mag, with_max=with_max, sink=sink,
        plan=plan, stage_sink=stage_sink,
    )


def _stream_kernel(
    mask_ref, x_ref, prev_ref, prevmax_ref, o_ref, omax_ref, *,
    spec, variant, directions, bh, bw, h, w, padding, rgb, out_nms,
):
    """Masked-grid streaming body: per-tile recompute-or-splice.

    The delta dispatcher marks each tile changed/unchanged in an SMEM mask
    (``(N, gh, gw)`` int32, one flag per grid step). A changed tile runs
    the exact same math as :func:`_kernel`'s primary path; an unchanged
    tile splices the cached output tile and per-block max (both per-block
    maxima in the lane-row layout of ``_bmax_spec``) instead — one
    ``lax.cond`` per grid step, so Mosaic branches over the whole tile
    compute and the skipped tile costs only the (unavoidable) window DMA
    plus a VMEM copy. Splice == recompute bit-exactly because an unchanged
    input window reproduces identical arithmetic, inductively across
    frames.
    """
    k = pl.program_id(1)
    j = pl.program_id(2)
    changed = mask_ref[0, k, j] != 0

    def block_max(mag):
        return _block_max(mag, k, j, h=h, w=w, bh=bh, bw=bw)

    def fresh(x_raw):
        x = luma(x_raw, axis=0) if rgb else as_f32(x_raw)
        if out_nms:
            y = extend_tile(
                x, k, j, h=h, w=w, block_h=bh, block_w=bw,
                r=spec.radius + 1, padding=padding,
            )
            comps_ext = spec_components(
                y, spec, bh + 2, bw + 2, variant, directions
            )
            mag_ext = magnitude(comps_ext)
            comps = tuple(
                jax.lax.slice(g, (1, 1), (1 + bh, 1 + bw)) for g in comps_ext
            )
            thin = nms_thin(mag_ext, nms_sector(comps))
            mag = jax.lax.slice(mag_ext, (1, 1), (1 + bh, 1 + bw))
            return thin, block_max(mag)
        y = extend_tile(
            x, k, j, h=h, w=w, block_h=bh, block_w=bw, r=spec.radius,
            padding=padding,
        )
        mag = magnitude(spec_components(y, spec, bh, bw, variant, directions))
        return mag, block_max(mag)

    def cached(_x_raw):
        return prev_ref[0], prevmax_ref[0, 0]

    out, bmax = jax.lax.cond(changed, fresh, cached, x_ref[0])
    o_ref[0] = out
    omax_ref[0, 0] = bmax


# ---------------------------------------------------------------------------
# pallas_call wrapper (operates on the raw, unpadded batch)
# ---------------------------------------------------------------------------

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
        "pipeline_depth",
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
    pipeline_depth: int = 0,
    plan: "StencilPlan | str | None" = None,
    interpret: bool = False,
):
    """Fused megakernel on the raw batch — any registered operator, any (H, W).

    ``x``: ``(N, H, W)`` grayscale (u8 or f32), or ``(N, H, W, 3)`` RGB when
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

    ``precision="int"`` runs the exact integer lane (u8 gray input only;
    raises with the first failing eligibility gate otherwise — see
    ``repro.core.ladder``); outputs stay f32 and bit-identical to the
    default lane. ``pipeline_depth=0`` (default) uses Pallas's automatic
    double buffering; ``2..8`` switches to the manual DMA ring of that
    depth (:func:`_pipelined_kernel`), again bit-identical by construction.

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
    if pipeline_depth and not 2 <= pipeline_depth <= 8:
        raise ValueError(
            f"pipeline_depth must be 0 (automatic) or 2..8 (manual DMA "
            f"ring), got {pipeline_depth}"
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
        acc_dtype = (ladder.plan_accum_dtype(plan) if plan is not None
                     else ladder.accum_dtype(spec))
        if not interpret and acc_dtype == "int16":
            # Mosaic's 16-bit vector coverage is incomplete (e.g. no i16
            # neg); i32 holds every i16-bounded intermediate exactly, so
            # widening preserves bit-exactness. Interpret/XLA lanes keep
            # the narrow dtype the ladder licenses.
            acc_dtype = "int32"
    if rgb:
        n, h, w, _c = x.shape
    else:
        n, h, w = x.shape
    bh = block_h
    bw = block_w if block_w else w
    gh, gw = pl.cdiv(h, bh), pl.cdiv(w, bw)
    grid = (n, gh, gw)

    # NMS compares the magnitude against a 1-px neighborhood, so its input
    # window carries one extra ring on top of the (composed) stencil halo.
    reach = plan.linear_reach if plan is not None else spec.radius
    r_in = window_radius(reach, out_nms)
    in_spec = window_spec(h, w, bh, bw, r_in, channels=3 if rgb else None)
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

    common = dict(
        spec=spec,
        variant=variant,
        directions=directions,
        bh=bh,
        bw=bw,
        h=h,
        w=w,
        padding=padding,
        rgb=rgb,
        out_components=out_components,
        out_nms=out_nms,
        out_mag=out_mag,
        with_max=with_max,
        acc_dtype=acc_dtype,
        plan=plan,
    )
    if pipeline_depth:
        # Manual DMA ring: input stays in ANY/HBM, the kernel copies each
        # clamped window itself (same window_origin offsets as in_spec's
        # index map — byte-identical reads). The grid must run sequentially
        # for cross-step prefetch to be legal, hence "arbitrary" semantics.
        th, tw = window_shape(h, w, bh, bw, r_in)
        n_sink = _sink_slots(variant, directions)
        # Gradient row-pass sink extents are relative to the gradient
        # stage's input tile — bh/bw plus the NMS ring plus the *gradient*
        # radius (pre-stages have already consumed the rest of the reach).
        eh = bh + (2 if out_nms else 0) + 2 * spec.radius
        ew = bw + (2 if out_nms else 0)
        buf_shape = (pipeline_depth,) + ((3,) if rgb else ()) + (th, tw)
        scratch = [
            pltpu.VMEM(buf_shape, x.dtype),
            pltpu.SemaphoreType.DMA((pipeline_depth,)),
        ]
        if n_sink:
            scratch.append(
                pltpu.VMEM((n_sink, eh, ew), _compute_dtype(acc_dtype))
            )
        # Inter-stage VMEM scratch: one buffer per pre-stage plane, sized
        # to that stage's (shrinking) output extent.
        pre_shapes = []
        if plan is not None:
            pad2 = 2 if out_nms else 0
            remaining = plan.linear_reach
            for stage in plan.pre_stages:
                remaining -= stage.radius
                pre_shapes.append(
                    (1, bh + pad2 + 2 * remaining, bw + pad2 + 2 * remaining)
                )
        for shp in pre_shapes:
            scratch.append(pltpu.VMEM(shp, _compute_dtype(acc_dtype)))
        kernel = functools.partial(
            _pipelined_kernel, **common,
            depth=pipeline_depth, th=th, tw=tw, n_sink=n_sink,
            n_pre=len(pre_shapes),
        )
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3
            ),
            interpret=interpret,
        )(pad_to_windows(x, bh, bw, r_in))
    else:
        kernel = functools.partial(_kernel, **common)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[in_spec],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
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
    if rgb:
        n, h, w, _c = x.shape
    else:
        n, h, w = x.shape
    bh = block_h
    bw = block_w if block_w else w
    gh, gw = pl.cdiv(h, bh), pl.cdiv(w, bw)
    if prev_bmax.shape != (n, gh, gw) or mask.shape != (n, gh, gw):
        raise ValueError(
            f"prev_bmax/mask {prev_bmax.shape}/{mask.shape} do not match the "
            f"({n}, {gh}, {gw}) tile grid of block ({bh}, {bw})"
        )
    grid = (n, gh, gw)

    r_in = window_radius(spec.radius, out_nms)
    in_spec = window_spec(h, w, bh, bw, r_in, channels=3 if rgb else None)
    mask_spec = pl.BlockSpec(
        (1, gh, gw), lambda i, k, j: (i, 0, 0), memory_space=pltpu.SMEM
    )
    plane = pl.BlockSpec((1, bh, bw), lambda i, k, j: (i, k, j))

    kernel = functools.partial(
        _stream_kernel,
        spec=spec,
        variant=variant,
        directions=directions,
        bh=bh,
        bw=bw,
        h=h,
        w=w,
        padding=padding,
        rgb=rgb,
        out_nms=out_nms,
    )
    primary, bmax = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[mask_spec, in_spec, plane, _bmax_spec()],
        out_specs=[plane, _bmax_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, w), jnp.float32),
            _bmax_shape(n, gh, gw),
        ],
        interpret=interpret,
    )(
        mask.astype(jnp.int32),
        _planar(x, rgb),
        prev_primary,
        _bmax_pack(prev_bmax),
    )
    return primary, _bmax_unpack(bmax)
