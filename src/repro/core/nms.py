"""Gradient post-processing: non-maximum suppression + hysteresis linking.

The paper stops at gradient magnitude; a detector needs thin, binary edges.
This module is the pure-XLA reference for the output stage the fused Pallas
megakernel also runs (``repro.kernels.edge`` with ``out_nms=True``):

  * **Direction-aware NMS.** A pixel survives only if its magnitude is a
    local maximum along the gradient direction. With the paper's
    four-directional operator the sector is an *exact argmax* over the four
    directional responses ``(|G_x|, |G_y|, |G_d|, |G_dt|)`` — no
    orientation quantization, no interpolation (the usual Canny hack for
    2-directional operators). For 2-direction operators the sector falls
    back to the classical quantized-``atan2`` rule, implemented as pure
    comparisons against ``tan(pi/8)`` so it stays bit-exact across
    backends.
  * **Double-threshold + hysteresis.** ``thin > high`` seeds strong edges;
    strong edges grow through their 8-neighborhood into the ``thin > low``
    weak set until fixpoint (``lax.while_loop`` over a dilate-and-mask
    step). Thresholds are *fractions of the per-image magnitude peak* —
    scale-free, so one config works for any operator's gain. Strict ``>``
    (not ``>=``) keeps all-zero/constant frames edge-free even though their
    peak (and hence both absolute thresholds) is 0.

Bit-exactness: :func:`nms_sector` and :func:`nms_thin` are shared verbatim
by this XLA reference and the Pallas kernel body (the same construction as
``core.sobel.spec_components``): comparisons, selections and slices only —
no operation whose rounding could differ between backends — so the fused
kernel's thin map is bit-identical to :func:`thin_map` by construction.

The magnitude neighborhood needs one extra ring: :func:`thin_map` pads the
image by ``radius + 1`` and evaluates the component ladder on the
``(H+2, W+2)`` extended output so NMS at the image border compares against
the magnitude *of the boundary-extended image* — exactly what the kernel's
``radius + 1`` halo window produces per tile.

Hysteresis is deliberately NOT fused into the kernel: linking is a global
fixpoint (an edge chain may cross every tile — and, sharded, every device),
so it runs post-gather on the assembled thin map. See DESIGN.md §7.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sobel import magnitude, plan_components, spec_components

__all__ = [
    "DEFAULT_LOW",
    "DEFAULT_HIGH",
    "TEMPORAL_FLOOR",
    "nms_sector",
    "nms_thin",
    "thin_map",
    "resolve_thresholds",
    "hysteresis",
    "temporal_seeds",
    "update_seed_strength",
]

# Auto double-threshold defaults: fractions of the per-image magnitude peak.
DEFAULT_LOW = 0.10
DEFAULT_HIGH = 0.20

# Temporal hysteresis: a past edge keeps seeding while its decayed strength
# stays strictly above this floor, i.e. for floor(log(TEMPORAL_FLOOR) /
# log(decay)) frames after it was last detected (0 frames when decay == 0).
TEMPORAL_FLOOR = 0.5

# tan(pi/8): the sector boundary of the classical quantized-orientation NMS
# (gradient within 22.5 degrees of an axis snaps to that axis).
_TAN_PI8 = math.tan(math.pi / 8.0)


def nms_sector(comps: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """int32 gradient-sector map from the direction components.

    Sector codes name the magnitude neighbors NMS compares against
    (image convention: row axis grows downward):

      * 0 — horizontal gradient: west/east neighbors ``(y, x -+ 1)``.
      * 1 — vertical gradient: north/south neighbors ``(y -+ 1, x)``.
      * 2 — main diagonal (the K_d orientation, response grows toward
        bottom-right): neighbors ``(y -+ 1, x -+ 1)``.
      * 3 — anti-diagonal (K_dt): neighbors ``(y -+ 1, x +- 1)``.

    With 4 components the sector is the argmax of the absolute responses
    (first index wins ties — ``jnp.argmax`` semantics, spelled as
    comparisons so Mosaic lowers it). With 2 components it is the
    quantized-orientation rule via ``tan(pi/8)`` comparisons; the diagonal
    picks sector 2 when G_x and G_y agree in sign (both-negative gradients
    still point along the main diagonal). Everything is comparisons and
    selects on bit-exact inputs, so the map is bit-exact across backends.
    """
    if len(comps) == 4:
        a0, a1, a2, a3 = (jnp.abs(g) for g in comps)
        s23 = jnp.where(a2 >= a3, jnp.int32(2), jnp.int32(3))
        s123 = jnp.where((a1 >= a2) & (a1 >= a3), jnp.int32(1), s23)
        return jnp.where((a0 >= a1) & (a0 >= a2) & (a0 >= a3),
                         jnp.int32(0), s123)
    if len(comps) != 2:
        raise ValueError(f"nms_sector needs 2 or 4 components, got {len(comps)}")
    gx, gy = comps
    ax, ay = jnp.abs(gx), jnp.abs(gy)
    t = jnp.float32(_TAN_PI8)
    diag = jnp.where((gx >= 0) == (gy >= 0), jnp.int32(2), jnp.int32(3))
    return jnp.where(ay <= t * ax, jnp.int32(0),
                     jnp.where(ax <= t * ay, jnp.int32(1), diag))


def nms_thin(mag_ext: jnp.ndarray, sector: jnp.ndarray) -> jnp.ndarray:
    """Suppress non-maxima: ``(..., H+2, W+2)`` magnitude + ``(..., H, W)``
    sector map -> ``(..., H, W)`` thin magnitude.

    ``mag_ext`` carries a one-pixel ring of boundary-extended magnitude
    around the image (see :func:`thin_map` / the kernel's ``radius + 1``
    halo). A pixel is kept when its magnitude is ``>=`` both neighbors
    along its sector; suppressed pixels become exactly 0. Pure
    slice/compare/select — bit-exact across backends.
    """
    h, w = sector.shape[-2], sector.shape[-1]

    def sl(dr: int, dc: int) -> jnp.ndarray:
        y = jax.lax.slice_in_dim(mag_ext, 1 + dr, 1 + dr + h, axis=-2)
        return jax.lax.slice_in_dim(y, 1 + dc, 1 + dc + w, axis=-1)

    c = sl(0, 0)
    n1 = jnp.where(sector == 0, sl(0, -1),
         jnp.where(sector == 1, sl(-1, 0),
         jnp.where(sector == 2, sl(-1, -1), sl(-1, 1))))
    n2 = jnp.where(sector == 0, sl(0, 1),
         jnp.where(sector == 1, sl(1, 0),
         jnp.where(sector == 2, sl(1, 1), sl(1, -1))))
    keep = (c >= n1) & (c >= n2)
    return jnp.where(keep, c, jnp.float32(0.0))


def _pad_ext(x: jnp.ndarray, r: int, padding: str) -> jnp.ndarray:
    mode = {"reflect": "reflect", "edge": "edge", "zero": "constant"}
    if padding not in mode:
        raise ValueError(
            f"unknown padding {padding!r}; expected one of {tuple(mode)}"
        )
    widths = [(0, 0)] * (x.ndim - 2) + [(r, r), (r, r)]
    return jnp.pad(x, widths, mode=mode[padding])


def thin_map(
    gray: jnp.ndarray,
    spec: "F.OperatorSpec",
    *,
    variant: str,
    directions: int,
    padding: str = "reflect",
    precision: str = "f32",
    plan: "F.StencilPlan | None" = None,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...], jnp.ndarray]:
    """Pure-XLA reference for the fused gray->[pre-stages]->Sobel->NMS stage.

    ``gray``: ``(..., H, W)`` float32 grayscale. ``variant``/``directions``
    must already be resolved against ``spec``. Returns ``(thin, comps,
    mag)``: the ``(..., H, W)`` thin magnitude, the center per-direction
    components, and the center (un-thinned) magnitude — the peak source for
    normalization/thresholds, identical to the non-NMS pipeline's.

    The pad radius is the composed linear reach + 1 (``spec.radius + 1``
    for single-operator runs, ``plan.linear_reach + 1`` when ``plan``
    chains pre-stages): the component ladder runs on the ``(H+2, W+2)``
    extended output so the NMS neighborhood exists at the image border,
    mirroring the kernel's grown halo window (DESIGN.md §7, §12).

    ``precision="int"`` runs the gradient ladder in the exact integer
    accumulation dtype ``repro.core.ladder`` proves (the caller must have
    gated eligibility: u8/u16 gray, integer taps, budget fits); the
    components are cast to f32 before the magnitude/NMS stage, which stays
    f32 by contract — bit-identical to the default lane.
    """
    h, w = gray.shape[-2], gray.shape[-1]
    reach = plan.linear_reach if plan is not None else spec.radius
    if precision == "int":
        from repro.core import ladder

        acc = (ladder.plan_accum_dtype(plan, input_dtype=gray.dtype)
               if plan is not None
               else ladder.accum_dtype(spec, input_dtype=gray.dtype))
        if acc is None:
            raise ValueError(
                f"precision='int' unavailable for operator {spec.name!r}"
            )
        xp = _pad_ext(gray.astype(jnp.dtype(acc)), reach + 1, padding)
    else:
        xp = _pad_ext(gray.astype(jnp.float32), reach + 1, padding)
    if plan is not None:
        comps_ext = plan_components(xp, plan, h + 2, w + 2, variant, directions)
    else:
        comps_ext = spec_components(xp, spec, h + 2, w + 2, variant, directions)
    if precision == "int":
        comps_ext = tuple(c.astype(jnp.float32) for c in comps_ext)
    mag_ext = magnitude(comps_ext)

    def center(a: jnp.ndarray) -> jnp.ndarray:
        y = jax.lax.slice_in_dim(a, 1, 1 + h, axis=-2)
        return jax.lax.slice_in_dim(y, 1, 1 + w, axis=-1)

    comps = tuple(center(g) for g in comps_ext)
    thin = nms_thin(mag_ext, nms_sector(comps))
    return thin, comps, center(mag_ext)


def resolve_thresholds(
    peak: jnp.ndarray,
    low: Optional[float] = None,
    high: Optional[float] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Absolute (low, high) thresholds from peak fractions.

    ``peak`` is the per-image max of the un-thinned magnitude (any
    broadcastable shape, e.g. ``(B, 1, 1)``); ``low``/``high`` are
    fractions of it, defaulting to :data:`DEFAULT_LOW`/:data:`DEFAULT_HIGH`.
    A zero peak (blank/constant frame) yields zero thresholds — harmless,
    because :func:`hysteresis` thresholds with strict ``>``.
    """
    lo = DEFAULT_LOW if low is None else low
    hi = DEFAULT_HIGH if high is None else high
    peak = jnp.asarray(peak, jnp.float32)
    return peak * jnp.float32(lo), peak * jnp.float32(hi)


def _dilate8(m: jnp.ndarray) -> jnp.ndarray:
    """8-neighborhood boolean dilation (includes the center; zero ring)."""
    p = jnp.pad(m, [(0, 0)] * (m.ndim - 2) + [(1, 1), (1, 1)])
    h, w = m.shape[-2], m.shape[-1]
    acc = None
    for dr in (-1, 0, 1):
        for dc in (-1, 0, 1):
            y = jax.lax.slice_in_dim(p, 1 + dr, 1 + dr + h, axis=-2)
            y = jax.lax.slice_in_dim(y, 1 + dc, 1 + dc + w, axis=-1)
            acc = y if acc is None else acc | y
    return acc


def hysteresis(
    thin: jnp.ndarray,
    low: jnp.ndarray,
    high: jnp.ndarray,
    seed: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Double-threshold + iterative-until-fixpoint edge linking.

    ``thin``: ``(..., H, W)`` NMS-suppressed magnitude. ``low``/``high``:
    *absolute* thresholds broadcastable against it (see
    :func:`resolve_thresholds`). Strong pixels (``thin > high``) are edges;
    weak pixels (``thin > low``) become edges when 8-connected to an edge,
    transitively — a monotone dilate-and-mask loop run to fixpoint, so the
    result is the exact connected-component answer, independent of tiling
    or sharding. Returns a bool edge map.

    ``seed`` (optional bool map, broadcastable) adds extra strong seeds —
    the temporal-hysteresis hook: pixels that were edges in recent frames
    (see :func:`temporal_seeds`) seed this frame's linking, but only where
    the current frame is at least weak, so a seed can never resurrect a
    pixel with no present-day evidence. ``seed=None`` and an all-``False``
    seed produce bit-identical results (``strong | (False & weak) ==
    strong``), which is what makes ``decay=0`` streaming exactly equal to
    stateless per-frame detection.

    Runs in pure XLA on the gathered thin map — linking is global (a chain
    may cross every shard), which is why this stage stays post-gather even
    when the NMS ran fused in the kernel (DESIGN.md §7).
    """
    weak = thin > low
    strong = (thin > high) & weak  # guard against low > high configs
    if seed is not None:
        strong = strong | (seed & weak)

    def cond(state):
        return state[1]

    def body(state):
        cur, _ = state
        grown = _dilate8(cur) & weak
        return grown, jnp.any(grown != cur)

    edges, _ = jax.lax.while_loop(cond, body, (strong, jnp.bool_(True)))
    return edges


def temporal_seeds(
    strength: jnp.ndarray, decay: float
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Decay the per-pixel temporal seed strength by one frame.

    ``strength``: ``(..., H, W)`` float32 — 1.0 where the previous frame
    detected an edge, geometrically decayed where it did not (see
    :func:`update_seed_strength`). Returns ``(seed, decayed)``:

      * ``seed``    — bool map of pixels still strong enough
        (``decayed > TEMPORAL_FLOOR``) to seed this frame's linking.
      * ``decayed`` — ``strength * decay``, the strength the update step
        folds this frame's edges into.

    ``decay=0`` zeroes the strength before the strict-``>`` floor test, so
    no seed ever fires and streaming collapses to stateless detection.
    """
    decayed = strength * jnp.float32(decay)
    return decayed > jnp.float32(TEMPORAL_FLOOR), decayed


def update_seed_strength(
    decayed: jnp.ndarray, edges: jnp.ndarray
) -> jnp.ndarray:
    """Fold this frame's edges into the decayed strength map.

    A re-detected pixel snaps back to full strength 1.0 (its persistence
    age resets); everything else keeps its decayed value until it falls
    through :data:`TEMPORAL_FLOOR` and stops seeding.
    """
    return jnp.maximum(edges.astype(jnp.float32), decayed)
