"""repro.api — the single user-facing facade for the edge-detection stack.

One call::

    from repro.api import EdgeConfig, edge_detect

    result = edge_detect(frames, EdgeConfig(operator="scharr3"))
    result.magnitude      # (..., H, W) edge image
    result.orientation    # present when with_orientation=True
    result.components     # (..., D, H, W) when with_components=True
    result.peak           # (...,) per-image max when with_max/normalize
    result.thin           # NMS-thinned magnitude when nms=True
    result.edges          # (..., H, W) bool edge map when hysteresis=True

:class:`EdgeConfig` is one frozen dataclass — operator (any name in the
``repro.core.filters`` registry) or multi-stage :class:`StencilPlan`
(``plan="canny5"`` for the fused Gaussian5 -> Sobel5 -> NMS chain),
directions, variant, padding, backend, block overrides, and output
selection — threaded verbatim through ``repro.kernels.dispatch`` down to
the Pallas megakernel / XLA reference. :class:`EdgeResult` is a structured
output; both are registered pytrees, so the facade composes with
``jax.jit``/``vmap``/sharding.

Input layout is auto-detected (``HW`` / ``HWC`` / ``NHW`` / ``NHWC`` /
batched video ``NTHW``/``NTHWC``): a trailing dimension of exactly 3 on a
>= 3-D input is treated as RGB channels; everything before the spatial
``(H, W)`` pair is batch. Pass ``layout=`` to override (e.g. a genuine
3-pixel-wide grayscale image).

This module IS the entry point: the historical shims
(``repro.core.pipeline.edge_detect``, ``repro.kernels.dispatch.{sobel,
edge_detect}``, ``repro.kernels.ops.{sobel,edge_pipeline}``) were removed
with the stencil-platform refactor — see README "Migrating from the legacy
entry points".
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.filters import SobelParams, StencilPlan, get_operator, resolve_plan
from repro.sharding.halo import ShardConfig

__all__ = [
    "EdgeConfig",
    "EdgeResult",
    "ShardConfig",
    "StreamState",
    "edge_detect",
    "edge_detect_stream",
    "detect_layout",
    "LAYOUTS",
]

# Recognized canonical layouts, in detection order of dims.
LAYOUTS = ("HW", "HWC", "NHW", "NHWC", "NTHW", "NTHWC")


def detect_layout(shape: Tuple[int, ...]) -> str:
    """Canonical layout string for an input shape.

    Rule: a trailing dim of exactly 3 on a >= 3-D input is the RGB channel
    axis; the last two remaining dims are ``(H, W)``; every leading dim is
    batch (``N``, then ``T`` for video stacks). 2-D input is one grayscale
    image.
    """
    ndim = len(shape)
    rgb = ndim >= 3 and shape[-1] == 3
    spatial = ndim - (1 if rgb else 0)
    if spatial < 2:
        raise ValueError(f"cannot interpret shape {shape} as image(s)")
    batch = spatial - 2
    # 0/1/2 batch dims get the canonical names; deeper stacks are still
    # accepted (every leading dim is batch) under a generic "N..." prefix.
    prefix = ("", "N", "NT")[batch] if batch <= 2 else "N" * batch
    return prefix + "HW" + ("C" if rgb else "")


@dataclasses.dataclass(frozen=True)
class EdgeConfig:
    """Everything one edge-detection call needs, in one frozen value.

    Fields:
      operator:   registered operator name (``sobel5`` | ``sobel3`` |
                  ``scharr3`` | ``prewitt3`` | ``sobel7`` | custom).
      plan:       multi-stage :class:`~repro.core.filters.StencilPlan` —
                  a registered plan name (``canny5`` | ``blur_sobel5``) or
                  a :class:`StencilPlan` value. The plan is the single
                  source of truth for the whole stencil chain: it
                  overrides ``operator`` (the resolved config pins
                  ``operator`` to the plan's gradient stage), composes the
                  halo from every stage radius, and — when it ends in an
                  ``nms`` stage — forces ``nms=True``. The entire chain
                  runs as ONE fused Pallas launch (or the equivalent
                  staged XLA reference), bit-exact across backends/meshes.
      directions: direction count; 0 = the operator's maximum.
      variant:    algorithmic variant (``direct``/``separable``/``v1``/``v2``);
                  ``auto`` = the operator's best. Unsupported ladder variants
                  coerce down (all variants are mathematically identical).
      params:     custom generalized weights (Sobel-5x5 family; paper §3.2).
      padding:    boundary rule: ``reflect`` | ``edge`` | ``zero``.
      normalize:  scale magnitude into [0, 255] per image (display form).
      backend:    ``auto`` | ``pallas-tpu`` | ``pallas-interpret`` | ``xla``;
                  None = auto. Outputs are bit-exact across backends.
      block_h/block_w: Pallas tile override; None = tuning cache / default.
      precision:  arithmetic lane: ``auto`` | ``f32`` | ``int``. ``int`` is
                  the exact low-precision lane — u8 or u16 gray frames x
                  integer taps accumulated in the i16/i32 budget
                  ``repro.core.ladder`` proves for the frame's dtype, f32
                  only from the magnitude/NMS stage on — *bit-identical*
                  to the f32 lane (it raises when the proof does not cover
                  the workload: RGB, other input dtypes, fractional taps,
                  a bound past 2^24 such as sobel7's on u16 frames).
                  ``auto`` opts eligible workloads in on the Pallas
                  backends and stays f32 on XLA
                  (``repro.kernels.dispatch.resolve_precision``).
      shard:      :class:`~repro.sharding.halo.ShardConfig` — spread the call
                  over the image mesh ``(data, row, col)`` with halo
                  exchange between spatial neighbors; None = single device.
                  Sharded outputs are bit-exact with single-device ones.
      nms:        direction-aware non-maximum suppression: ``magnitude``
                  (and ``thin``) become the thinned edge map — suppressed
                  pixels are exactly 0. Fused into the Pallas megakernel
                  (the halo grows by one ring); bit-exact with the XLA
                  reference (``repro.core.nms``) on every backend/mesh.
      hysteresis: double-threshold + connected-edge linking on the thin map
                  (implies ``nms``); sets ``EdgeResult.edges`` (bool).
                  Linking is global, so it always runs post-gather in XLA.
      low, high:  hysteresis thresholds as *fractions of the per-image
                  magnitude peak* (scale-free across operators/inputs);
                  None = 0.10 / 0.20 (``repro.core.nms.DEFAULT_LOW/HIGH``).
      temporal:   temporal hysteresis for video streams (implies
                  ``hysteresis``): edges detected in recent frames seed the
                  current frame's linking wherever the current thin map is
                  at least weak, so detections persist instead of
                  flickering. Streaming-only — carried per-stream state, so
                  plain :func:`edge_detect` rejects it; use
                  :func:`edge_detect_stream` / ``repro.serve.streams``.
      decay:      per-frame geometric decay of the temporal seed strength
                  in [0, 1]: a past edge keeps seeding while
                  ``decay^age > TEMPORAL_FLOOR`` (``repro.core.nms``).
                  ``decay=0`` makes streaming output bit-identical to
                  stateless per-frame detection (the tested contract).
      with_components:  also return per-direction gradients ``(..., D, H, W)``.
      with_orientation: also return gradient orientation ``atan2(G_y, G_x)``.
      with_max:         also return the per-image peak of the unnormalized
                        (un-thinned) magnitude (free on the fused Pallas
                        path).
    """

    operator: str = "sobel5"
    plan: "str | StencilPlan | None" = None
    directions: int = 0
    variant: str = "auto"
    params: Optional[SobelParams] = None
    padding: str = "reflect"
    normalize: bool = True
    backend: Optional[str] = None
    block_h: Optional[int] = None
    block_w: Optional[int] = None
    precision: str = "auto"
    shard: Optional[ShardConfig] = None
    nms: bool = False
    hysteresis: bool = False
    low: Optional[float] = None
    high: Optional[float] = None
    temporal: bool = False
    decay: float = 0.0
    with_components: bool = False
    with_orientation: bool = False
    with_max: bool = False

    def replace(self, **kw) -> "EdgeConfig":
        return dataclasses.replace(self, **kw)

    def resolved(self) -> "EdgeConfig":
        """Fill ``auto``/0 fields from the operator spec and validate.

        Idempotent; raises for unknown operators, unsupported directions,
        unknown variants, or malformed hysteresis thresholds. Requesting
        ``hysteresis`` auto-enables ``nms`` (linking operates on the thin
        map) and pins concrete ``low``/``high`` fractions. The resolved
        config is what gets threaded through dispatch -> kernels (and
        recorded in :class:`EdgeResult`).
        """
        from repro.core import nms as _nms

        if self.precision not in ("auto", "f32", "int"):
            raise ValueError(
                f"unknown precision {self.precision!r}; expected 'auto', "
                "'f32' or 'int'"
            )
        if not 0.0 <= self.decay <= 1.0:
            raise ValueError(
                f"decay={self.decay} must be a per-frame attenuation in [0, 1]"
            )
        if self.decay and not self.temporal:
            raise ValueError(
                "decay is the temporal-hysteresis attenuation; set "
                "temporal=True (stateless calls carry no seed state) or "
                "leave it 0"
            )
        hysteresis = self.hysteresis or self.temporal
        low, high = self.low, self.high
        if not hysteresis and (low is not None or high is not None):
            if (low, high) == (_nms.DEFAULT_LOW, _nms.DEFAULT_HIGH):
                # A resolved hysteresis config pinned the defaults; toggling
                # hysteresis off (e.g. edge_detect(x, cfg, hysteresis=False)
                # to reuse a detector config for magnitude) clears them.
                low = high = None
            else:
                raise ValueError(
                    "low/high are hysteresis thresholds; set hysteresis=True "
                    "(nms alone never thresholds) or leave them unset"
                )
        if hysteresis:
            low = _nms.DEFAULT_LOW if low is None else low
            high = _nms.DEFAULT_HIGH if high is None else high
        for name, v in (("low", low), ("high", high)):
            if v is not None and not 0.0 <= v <= 1.0:
                raise ValueError(
                    f"{name}={v} must be a fraction of the magnitude peak "
                    "in [0, 1]"
                )
        if low is not None and high is not None and low > high:
            raise ValueError(f"low={low} must not exceed high={high}")
        plan = resolve_plan(self.plan)
        if plan is not None:
            spec = plan.gradient
            if spec is None:
                raise ValueError(
                    f"plan {plan.name!r} has no gradient stage; the edge "
                    "engine emits direction components (append a gradient "
                    "operator stage)"
                )
            if (self.nms or hysteresis) and not plan.nms:
                raise ValueError(
                    f"plan gate 'nms-stage': plan {plan.name!r} has no "
                    "trailing 'nms' stage but nms/hysteresis was requested; "
                    "the plan is the single source of truth — append 'nms' "
                    "to its stages"
                )
            operator = spec.name
            nms = plan.nms or hysteresis
        else:
            spec = get_operator(self.operator, self.params)
            operator = self.operator
            nms = self.nms or hysteresis
        return self.replace(
            plan=plan,
            operator=operator,
            directions=spec.resolve_directions(self.directions),
            variant=spec.resolve_variant(self.variant),
            nms=nms,
            hysteresis=hysteresis,
            low=low,
            high=high,
        )

    @property
    def spec(self):
        plan = resolve_plan(self.plan)
        if plan is not None and plan.gradient is not None:
            return plan.gradient
        return get_operator(self.operator, self.params)


# Config is pure static data — by-value (hashable) through jit, like a str.
jax.tree_util.register_static(EdgeConfig)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class EdgeResult:
    """Structured output of :func:`edge_detect`.

    ``magnitude`` is always present; the optional fields mirror the
    ``with_*``/``nms``/``hysteresis`` output selection of
    :class:`EdgeConfig`. When ``config.nms`` is set, ``magnitude`` *is* the
    NMS-thinned map (the fused kernel emits it in one pass) and ``thin``
    aliases it; ``peak`` stays the per-image max of the un-thinned
    magnitude either way. ``layout`` is the detected (or overridden) input
    layout; ``config`` is the fully resolved :class:`EdgeConfig` that
    produced the result.
    """

    magnitude: jnp.ndarray                     # (..., H, W) f32
    components: Optional[jnp.ndarray] = None   # (..., D, H, W) f32
    orientation: Optional[jnp.ndarray] = None  # (..., H, W) f32, radians
    peak: Optional[jnp.ndarray] = None         # (...,) f32 per-image max
    thin: Optional[jnp.ndarray] = None         # (..., H, W) f32, nms=True
    edges: Optional[jnp.ndarray] = None        # (..., H, W) bool, hysteresis
    skipped: Optional[jnp.ndarray] = None      # (...,) i32 delta-skipped tiles
    layout: str = "HW"
    config: Optional[EdgeConfig] = None

    def tree_flatten(self):
        leaves = (self.magnitude, self.components, self.orientation,
                  self.peak, self.thin, self.edges, self.skipped)
        return leaves, (self.layout, self.config)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        layout, config = aux
        (magnitude, components, orientation, peak, thin, edges,
         skipped) = leaves
        return cls(magnitude, components, orientation, peak, thin, edges,
                   skipped, layout, config)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StreamState:
    """Per-stream temporal state carried between frames of one video stream.

    The leaves cache exactly what the delta-skip and temporal-hysteresis
    machinery needs from frame ``t - 1`` (all batched ``(B, ...)`` — one
    slice per stream when the engine batches same-resolution streams):

      * ``frame``   — the previous input frames in kernel dtype (u8 stays
        u8), the reference for the exact per-tile change test.
      * ``primary`` — the previous *un-normalized* primary map (the NMS
        thin magnitude when ``nms``, else the magnitude): the splice source
        for delta-skipped tiles.
      * ``bmax``    — the previous per-block maxima ``(B, gh, gw)``: cached
        block-max output of the fused kernel, spliced per-tile so the global
        peak (normalization + hysteresis thresholds) stays exact.
      * ``seed``    — the temporal seed-strength map (``config.temporal``;
        ``None`` otherwise): 1.0 at last frame's edges, geometrically
        decayed elsewhere (``repro.core.nms.update_seed_strength``).

    ``block`` (static aux) pins the ``(block_h, block_w)`` delta-tile grid
    so every frame of a stream tiles identically — a mid-stream tuning
    change cannot silently misalign the cached ``bmax``/mask grids.
    ``initialized`` is ``False`` for the zero state :func:`init` returns;
    the first frame then recomputes every tile regardless of the (zero)
    ``frame`` cache.
    """

    frame: Optional[jnp.ndarray]
    primary: Optional[jnp.ndarray]
    bmax: Optional[jnp.ndarray]
    seed: Optional[jnp.ndarray]
    block: Tuple[int, int] = (0, 0)
    initialized: bool = False

    def tree_flatten(self):
        return ((self.frame, self.primary, self.bmax, self.seed),
                (self.block, self.initialized))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        block, initialized = aux
        frame, primary, bmax, seed = leaves
        return cls(frame, primary, bmax, seed, block, initialized)

    @property
    def grid(self) -> Tuple[int, int]:
        """(gh, gw) delta-tile grid of the cached ``bmax``."""
        return self.bmax.shape[-2], self.bmax.shape[-1]

    @property
    def tiles(self) -> int:
        """Total delta tiles per frame (the denominator for skip rates)."""
        gh, gw = self.grid
        return gh * gw

    @classmethod
    def init(cls, batch, h, w, config: "EdgeConfig", *, rgb: bool = False,
             dtype=jnp.uint8) -> "StreamState":
        """Zero state for ``batch`` streams of ``(h, w)`` frames.

        The first :func:`edge_detect_stream` call on it recomputes every
        tile (``initialized=False`` forces an all-changed mask), filling
        the caches; callers never need to special-case frame 0.
        """
        from repro.kernels import dispatch

        config = config.resolved()
        bh, bw = dispatch.stream_block_shape(h, w, config, rgb=rgb)
        gh, gw = -(-h // bh), -(-w // bw)
        shape = (batch, h, w, 3) if rgb else (batch, h, w)
        return cls(
            frame=jnp.zeros(shape, dtype),
            primary=jnp.zeros((batch, h, w), jnp.float32),
            bmax=jnp.zeros((batch, gh, gw), jnp.float32),
            seed=(jnp.zeros((batch, h, w), jnp.float32)
                  if config.temporal else None),
            block=(bh, bw),
            initialized=False,
        )


def edge_detect(
    images,
    config: Optional[EdgeConfig] = None,
    *,
    layout: Optional[str] = None,
    mesh=None,
    **overrides,
) -> EdgeResult:
    """Run the full edge-detection pipeline on ``images``.

    Args:
      images: ``HW`` / ``HWC`` / ``NHW`` / ``NHWC`` grayscale or RGB images,
        or batched video stacks (``NTHW`` / ``NTHWC``); u8 or float.
      config: an :class:`EdgeConfig`; None = defaults.
      layout: explicit layout override (skips auto-detection) — the escape
        hatch for ambiguous shapes, e.g. a ``(3, H, W)`` grayscale batch
        whose trailing dim happens to be 3.
      mesh: concrete image mesh (axes ``data``/``row``/``col``) overriding
        ``config.shard`` — for callers that manage the device population
        themselves (elastic serving).
      **overrides: convenience — field overrides applied to ``config`` via
        ``dataclasses.replace`` (e.g. ``edge_detect(x, operator="scharr3")``).

    Returns:
      :class:`EdgeResult` with batch dims mirroring the input's.
    """
    from repro.kernels import dispatch

    cfg = (config or EdgeConfig())
    if overrides:
        cfg = cfg.replace(**overrides)
    cfg = cfg.resolved()
    images = jnp.asarray(images)
    layout = layout or detect_layout(images.shape)
    return dispatch.edge(images, cfg, layout=layout, mesh=mesh)


def edge_detect_stream(
    frames,
    config: Optional[EdgeConfig] = None,
    state: Optional[StreamState] = None,
    *,
    layout: Optional[str] = None,
    **overrides,
) -> Tuple[EdgeResult, StreamState]:
    """One frame step of the stateful streaming pipeline.

    ``frames`` is ONE frame per stream — ``HW`` / ``HWC`` for a single
    stream or ``NHW`` / ``NHWC`` for a batch of same-resolution streams
    (no video-stack ``T`` axis: time is the successive calls). ``state``
    is the previous call's :class:`StreamState` (``None`` = cold start).

    Returns ``(result, new_state)``. On top of the stateless pipeline the
    streaming path adds:

      * **Delta-skip tiles** — a per-tile exact change test against
        ``state.frame``; unchanged tiles splice the cached thin map and
        per-block maxima instead of recomputing (``result.skipped`` counts
        them per stream). When no tile of the batch changed, a branch on
        the device skips the frame compute outright. Output is
        bit-identical to full recompute.
      * **Temporal hysteresis** — with ``config.temporal``, recent frames'
        edges seed this frame's linking (decayed by ``config.decay``), so
        detections persist instead of flickering. ``decay=0`` is
        bit-identical to stateless per-frame :func:`edge_detect`.

    The call is fully traceable (``jax.jit`` over ``(frames, state)`` with
    the config static); ``repro.serve.streams.StreamEngine`` is the
    slot/admission scheduler that drives it for many concurrent streams.
    """
    from repro.kernels import dispatch

    cfg = (config or EdgeConfig())
    if overrides:
        cfg = cfg.replace(**overrides)
    cfg = cfg.resolved()
    frames = jnp.asarray(frames)
    layout = layout or detect_layout(frames.shape)
    return dispatch.edge_stream(frames, cfg, state, layout=layout)
