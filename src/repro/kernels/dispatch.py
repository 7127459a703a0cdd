"""Unified backend dispatch: one EdgeConfig-driven engine, three backends.

  * ``pallas-tpu``       — the fused zero-copy Pallas megakernel
                           (``repro.kernels.edge``), compiled by Mosaic.
  * ``pallas-interpret`` — the same kernel through the Pallas interpreter
                           (CPU correctness path; bit-exact vs the kernel).
  * ``xla``              — ``repro.core.sobel`` (pure XLA; fastest on CPU,
                           and the portable fallback everywhere else).

``backend=None``/``"auto"`` resolves to ``pallas-tpu`` on TPU hosts and
``xla`` elsewhere. For the Pallas backends, block shapes come from (in
order): explicit ``block_h``/``block_w`` config fields, the tuning cache
(``repro.kernels.tuning``, keyed by backend/dtype/operator/variant/padding/
layout/H/W), then a conservative default.

:func:`edge` is the engine under the ``repro.api`` facade: it takes the
*resolved* :class:`~repro.api.EdgeConfig` verbatim, routes to a backend,
and assembles the structured :class:`~repro.api.EdgeResult` (magnitude,
optional per-direction components / orientation / per-image peak, and —
with ``nms``/``hysteresis`` — the thin map and binary edge map; NMS runs
fused in the kernel, hysteresis always post-gather in XLA since linking is
global). All backends are mathematically identical; for integer-weight
taps the outputs are bit-exact across backends (see
``repro.core.sobel.magnitude``, ``repro.core.nms`` and
``repro.kernels.tiling.luma``).

When the config carries a :class:`~repro.sharding.halo.ShardConfig` (or an
explicit image ``mesh`` is passed), the same per-shard backend compute runs
under ``shard_map`` on the image mesh ``(data, row, col)`` with halo
exchange of the stencil radius between spatial neighbors
(``repro.sharding.halo``) — batch-sharded, spatially sharded, or both, and
bit-exact with the single-device engine for every backend.

A config with a multi-stage :class:`~repro.core.filters.StencilPlan`
(``EdgeConfig.plan``) routes through the same funnel: the composed reach
(sum of stage radii, plus the NMS ring) sizes the halo exchange and the
tuning-cache slot, and the whole chain runs as one fused Pallas launch /
one staged XLA closure per backend.
"""
from __future__ import annotations

import math
from typing import TYPE_CHECKING, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.filters import SobelParams, get_operator, plan_identity
from repro.core.sobel import magnitude as rss_magnitude
from repro.core.sobel import sobel_components as core_components
from repro.kernels import edge as ekern
from repro.kernels import tuning
from repro.kernels.tiling import window_radius

if TYPE_CHECKING:  # no runtime import: repro.api imports this module
    from repro.api import EdgeConfig, EdgeResult, StreamState

__all__ = [
    "BACKENDS",
    "resolve_backend",
    "resolve_precision",
    "choose_block_shape",
    "stream_block_shape",
    "edge",
    "stream_delta",
    "edge_stream",
    "edge_stream_cached",
]

BACKENDS = ("auto", "pallas-tpu", "pallas-interpret", "xla")


def resolve_backend(backend: Optional[str] = None) -> str:
    """Map user intent to a concrete backend name."""
    b = backend or "auto"
    if b not in BACKENDS:
        raise ValueError(f"unknown backend {b!r}; expected one of {BACKENDS}")
    if b == "auto":
        return "pallas-tpu" if jax.default_backend() == "tpu" else "xla"
    return b


def resolve_precision(
    precision: str, backend: str, *, spec, rgb: bool, input_dtype, plan=None
) -> str:
    """Resolve ``EdgeConfig.precision`` to the concrete lane: f32 | int.

    Explicit ``"int"`` works on every backend but raises (with the first
    failing gate from ``repro.core.ladder.int_lane_eligible`` — or the
    plan-level ``plan_int_eligible`` chain when ``plan`` is set) when the
    exactness proof does not cover the workload — fractional taps, a
    budget past 2^24, RGB input (fractional BT.601 luma), or frames that
    are neither u8 nor u16. ``"auto"`` opts eligible gray u8/u16 workloads
    into the integer lane on the Pallas backends only: on a TPU v5e the
    u16 integer lane's kernel takes 1.131 ms a 3000x4096 frame against the
    f32 lane's 1.147 ms. On XLA the f32 ladder is already the measured
    reference (and the committed benchmark baselines), so auto stays
    conservative there — the lane is still available explicitly.
    """
    from repro.core import ladder

    def eligible():
        if plan is not None:
            return ladder.plan_int_eligible(
                plan, rgb=rgb, input_dtype=input_dtype
            )
        return ladder.int_lane_eligible(
            spec, rgb=rgb, input_dtype=input_dtype
        )

    if precision == "f32":
        return "f32"
    if precision == "int":
        ok, reason = eligible()
        if not ok:
            raise ValueError(f"precision='int' unavailable: {reason}")
        return "int"
    if precision != "auto":
        raise ValueError(
            f"unknown precision {precision!r}; expected 'auto', 'f32' or "
            "'int'"
        )
    if backend == "xla":
        return "f32"
    ok, _reason = eligible()
    return "int" if ok else "f32"


def choose_block_shape(
    h: int,
    w: int,
    *,
    operator: str = "sobel5",
    variant: str = "v2",
    dtype: str = "float32",
    backend: str = "pallas-interpret",
    padding: str = "reflect",
    layout: str = "gray",
    block_h: Optional[int] = None,
    block_w: Optional[int] = None,
    cache: Optional[tuning.TuningCache] = None,
    devices: int = 1,
    mesh: str = "1x1x1",
    kernel_h: Optional[int] = None,
    kernel_w: Optional[int] = None,
    precision: str = "f32",
    pipeline_depth: Optional[int] = None,
    plan=None,
) -> Tuple[int, int, int, str]:
    """Resolve (block_h, block_w, depth, source) for a Pallas backend.

    ``source`` is ``"explicit"``, ``"tuned"`` or ``"default"`` — tests and
    benchmarks use it to verify the tuning cache actually steers dispatch.
    ``h``/``w`` key the cache on the user-visible frame; under spatial
    sharding ``kernel_h``/``kernel_w`` name the halo-extended local block
    the kernel actually tiles (they size the fallback default), and
    ``devices``/``mesh`` keep sharded tunings from colliding with
    single-device entries (TuneKey schema v4). ``precision`` (resolved
    lane) and ``pipeline_depth`` slot the v5 key dimensions: an explicit
    depth pins the returned depth (and its own cache slot); ``None`` lets
    a tuned entry supply the depth the sweep measured faster, defaulting
    to 0 (automatic pipelining). ``plan`` (a resolved
    :class:`~repro.core.filters.StencilPlan`) slots the v6 plan-identity
    dimension and sizes the fallback default by the composed reach.
    """
    if block_h and block_w:
        return block_h, block_w, pipeline_depth or 0, "explicit"
    cache = cache if cache is not None else tuning.get_default_cache()
    hit = cache.lookup(
        tuning.TuneKey(backend, dtype, operator, variant, h, w, padding,
                       layout, devices, mesh, precision, pipeline_depth or 0,
                       plan_identity(plan) if plan is not None else "-")
    )
    if hit is not None:
        bh, bw, depth = hit
        if pipeline_depth is not None:
            depth = pipeline_depth
        return block_h or bh, block_w or bw, depth, "tuned"
    size = (2 * plan.linear_reach + 1 if plan is not None
            else get_operator(operator).size)
    dbh, dbw = ekern.default_block_shape(
        kernel_h or h, kernel_w or w, size,
        channels=3 if layout == "rgb" else None,
    )
    return block_h or dbh, block_w or dbw, pipeline_depth or 0, "default"


def _kernel_dtype_name(x: jnp.ndarray) -> str:
    """Dtype the kernel will actually see in HBM (edge.py dtype policy)."""
    return x.dtype.name if x.dtype in ekern.RAW_DTYPES else "float32"


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

def _backend_compute(
    config, backend, *, rgb, need_comps, need_raw, block_h, block_w,
    precision="f32", pipeline_depth=0,
):
    """The backend compute: ``(B, h, w[, 3]) -> (primary, stacked
    components | None, raw magnitude | None)``.

    ``primary`` is the magnitude — or the NMS thin magnitude when
    ``config.nms``. ``need_raw`` additionally returns the un-thinned
    magnitude in NMS mode (the peak source; ``None`` whenever ``primary``
    already is the magnitude).

    Both engine branches run this same closure — single-device directly,
    sharded per-shard under ``shard_map`` — which is what makes
    sharded-vs-single bit-exactness hold per backend by construction. (The
    single-device magnitude+peak cases bypass it for the fused ``with_max``
    kernel; the sharded path computes its peak from the cropped raw
    magnitude instead, an exact max either way.)

    ``precision`` is the *resolved* lane (:func:`resolve_precision`);
    ``pipeline_depth`` the resolved DMA ring depth (0 = automatic; Pallas
    backends only — XLA has no DMA to pipeline).
    """
    if backend == "xla":
        from repro.core import nms
        from repro.core.pipeline import rgb_to_gray

        def run(xl):
            if precision == "int":
                # Eligibility (u8/u16 gray input) was proven by
                # resolve_precision; the ladder casts straight to the
                # accumulation dtype, so the frame is handed over raw.
                gray = xl
            else:
                gray = rgb_to_gray(xl) if rgb else xl.astype(jnp.float32)
            if config.nms:
                thin, ctuple, raw = nms.thin_map(
                    gray, config.spec, variant=config.variant,
                    directions=config.directions, padding=config.padding,
                    precision=precision, plan=config.plan,
                )
                stacked = jnp.stack(ctuple, axis=-3) if need_comps else None
                return thin, stacked, (raw if need_raw else None)
            ctuple = core_components(
                gray,
                operator=config.operator,
                directions=config.directions,
                variant=config.variant,
                params=config.params or SobelParams(),
                padding=config.padding,
                precision=precision,
                plan=config.plan,
            )
            mag = rss_magnitude(ctuple)
            return mag, (jnp.stack(ctuple, axis=-3) if need_comps else None), None

        return run

    kw = dict(
        operator=config.operator, variant=config.variant,
        params=config.params, directions=config.directions,
        padding=config.padding, block_h=block_h, block_w=block_w, rgb=rgb,
        precision=precision, pipeline_depth=pipeline_depth,
        plan=config.plan,
        interpret=(backend == "pallas-interpret"),
    )

    def run(xl):
        if config.nms:
            outs = ekern.edge_pallas(
                xl, out_nms=True, out_components=need_comps,
                out_mag=need_raw, **kw,
            )
            outs = list(outs) if isinstance(outs, tuple) else [outs]
            thin = outs.pop(0)
            stacked = outs.pop(0) if need_comps else None
            raw = outs.pop(0) if need_raw else None
            return thin, stacked, raw
        if need_comps:
            stacked = ekern.edge_pallas(xl, out_components=True, **kw)
            ctuple = tuple(
                jax.lax.index_in_dim(stacked, d, axis=1, keepdims=False)
                for d in range(config.directions)
            )
            return rss_magnitude(ctuple), stacked, None
        return ekern.edge_pallas(xl, **kw), None, None

    return run


def _edge_sharded(
    x, config, backend, mesh, *, rgb, h, w, need_comps, need_peak,
    tuning_cache, precision="f32", chaos=None,
):
    """Sharded engine body: returns ``(mag, comps|None, peak (B,1,1)|None)``
    bit-exact with the single-device branch.

    Both new kernel lanes compose with sharding unchanged: the halo
    exchange is dtype-preserving, so the per-shard kernel still sees raw
    u8/u16 (the integer lane's input contract), and the DMA ring tiles the
    halo-extended local block exactly like the automatic pipeline."""
    from repro.sharding import halo

    spec = config.spec
    # NMS reads a 1-px magnitude neighborhood on top of the operator
    # stencil, so the device-level halo grows to radius + 1, exactly like
    # the kernel's in-VMEM window (hysteresis, being a global fixpoint,
    # runs post-gather in :func:`edge` instead). A multi-stage plan
    # composes every stage radius into one exchange.
    r = halo.exchange_radius(spec, config.nms, plan=config.plan)
    d, rr, cc = mesh.shape["data"], mesh.shape["row"], mesh.shape["col"]
    sh, _hp = halo.shard_geometry(h, rr, r)
    sw, _wp = halo.shard_geometry(w, cc, r)
    he = sh + (2 * r if rr > 1 else 0)
    we = sw + (2 * r if cc > 1 else 0)

    bh = bw = None
    depth = 0
    if backend != "xla":
        bh, bw, depth, _src = choose_block_shape(
            h, w, operator=config.operator, variant=config.variant,
            dtype=_kernel_dtype_name(x), backend=backend,
            padding=config.padding, layout="rgb" if rgb else "gray",
            block_h=config.block_h, block_w=config.block_w,
            cache=tuning_cache,
            devices=d * rr * cc, mesh=f"{d}x{rr}x{cc}",
            kernel_h=he, kernel_w=we,
            precision=precision, pipeline_depth=config.pipeline_depth,
            plan=config.plan,
        )
    run = _backend_compute(
        config, backend, rgb=rgb, need_comps=need_comps,
        need_raw=config.nms and need_peak, block_h=bh, block_w=bw,
        precision=precision, pipeline_depth=depth,
    )
    mag, comps, peak = halo.sharded_edge(
        x, mesh, radius=r, padding=config.padding, compute=run,
        rgb=rgb, need_comps=need_comps, need_peak=need_peak, chaos=chaos,
    )
    if need_peak:
        peak = peak[:, None, None]
    return mag, comps, peak


def edge(
    images: jnp.ndarray,
    config: "EdgeConfig",
    *,
    layout: Optional[str] = None,
    tuning_cache: Optional[tuning.TuningCache] = None,
    mesh=None,
    chaos=None,
) -> "EdgeResult":
    """Run one resolved :class:`~repro.api.EdgeConfig` end to end.

    This is the single funnel every entry point (the ``repro.api`` facade,
    benchmarks, the serve loop) goes through: backend resolution, block-shape
    choice, the fused Pallas launch / XLA reference / sharded engine, and
    the assembly of the structured result. ``layout`` must name the input
    layout (the facade auto-detects it; see ``repro.api.detect_layout``).
    ``mesh`` (a concrete image mesh with axes ``data``/``row``/``col``)
    overrides ``config.shard`` — the serve loop passes the surviving-device
    mesh here after an elastic reshard. ``chaos`` (a
    ``repro.runtime.chaos.FaultPlan``) fires the ``"dispatch.edge"``
    injection site on entry — host-side Python, so under ``jax.jit`` it
    fires at trace time; per-request injection lives in the serve guard.
    """
    from repro.api import EdgeResult, detect_layout

    if chaos is not None:
        chaos.fire("dispatch.edge")
    config = config.resolved()
    if config.temporal:
        raise ValueError(
            "temporal hysteresis carries per-stream state; use "
            "repro.api.edge_detect_stream (or drop temporal for stateless "
            "calls)"
        )
    images = jnp.asarray(images)
    layout = layout or detect_layout(images.shape)
    rgb = layout.endswith("C")
    backend = resolve_backend(config.backend)

    x = ekern.kernel_dtype(images)
    if rgb:
        batch_shape = x.shape[:-3]
        h, w = x.shape[-3], x.shape[-2]
        x = x.reshape((-1, h, w, 3))
    else:
        batch_shape = x.shape[:-2]
        h, w = x.shape[-2], x.shape[-1]
        x = x.reshape((-1, h, w))

    need_comps = config.with_components or config.with_orientation
    # Hysteresis thresholds are fractions of the per-image magnitude peak.
    need_peak = config.normalize or config.with_max or config.hysteresis

    # Resolve the arithmetic lane once, against the dtype the kernel will
    # actually see — every downstream branch (fused fast path, backend
    # closure, sharded engine) then agrees on it.
    precision = resolve_precision(
        config.precision, backend, spec=config.spec, rgb=rgb,
        input_dtype=x.dtype, plan=config.plan,
    )

    if mesh is None and config.shard is not None:
        from repro.sharding import halo

        mesh = halo.mesh_from_config(config.shard)

    comps = None
    peak = None  # (B, 1, 1) while normalizing; squeezed into the result
    if mesh is not None and math.prod(mesh.shape.values()) > 1:
        mag, comps, peak = _edge_sharded(
            x, config, backend, mesh, rgb=rgb, h=h, w=w,
            need_comps=need_comps, need_peak=need_peak,
            tuning_cache=tuning_cache, precision=precision, chaos=chaos,
        )
    else:
        bh = bw = None
        depth = 0
        if backend != "xla":
            bh, bw, depth, _src = choose_block_shape(
                h, w, operator=config.operator, variant=config.variant,
                dtype=_kernel_dtype_name(x), backend=backend,
                padding=config.padding, layout="rgb" if rgb else "gray",
                block_h=config.block_h, block_w=config.block_w,
                cache=tuning_cache,
                precision=precision, pipeline_depth=config.pipeline_depth,
                plan=config.plan,
            )
        if backend != "xla" and need_peak:
            # Fused Pallas fast path: the kernel emits per-block maxima of
            # the (un-thinned) magnitude alongside whatever else the call
            # needs — thin map, components — so normalization and the
            # hysteresis thresholds need no second whole-image reduction
            # read. Max-of-block-maxes == max over the image (exact).
            kw = dict(
                operator=config.operator, variant=config.variant,
                params=config.params, directions=config.directions,
                padding=config.padding, block_h=bh, block_w=bw, rgb=rgb,
                precision=precision, pipeline_depth=depth,
                plan=config.plan,
                interpret=(backend == "pallas-interpret"),
            )
            if config.nms:
                outs = list(ekern.edge_pallas(
                    x, out_nms=True, out_components=need_comps,
                    with_max=True, **kw,
                ))
                mag = outs.pop(0)  # thin
                comps = outs.pop(0) if need_comps else None
            elif need_comps:
                stacked, bmax0 = ekern.edge_pallas(
                    x, out_components=True, with_max=True, **kw
                )
                outs = [bmax0]
                comps = stacked
                ctuple = tuple(
                    jax.lax.index_in_dim(stacked, d, axis=1, keepdims=False)
                    for d in range(config.directions)
                )
                mag = rss_magnitude(ctuple)
            else:
                mag, bmax0 = ekern.edge_pallas(x, with_max=True, **kw)
                outs = [bmax0]
            peak = jnp.max(outs[-1], axis=(-2, -1), keepdims=True)
        else:
            run = _backend_compute(
                config, backend, rgb=rgb, need_comps=need_comps,
                need_raw=config.nms and need_peak, block_h=bh, block_w=bw,
                precision=precision, pipeline_depth=depth,
            )
            mag, comps, raw = run(x)
            if need_peak:
                peak = jnp.max(
                    raw if raw is not None else mag, axis=(-2, -1),
                    keepdims=True,
                )

    orientation = None
    if config.with_orientation:
        # atan2 on bit-identical (G_y, G_x) — bit-exact across backends.
        # comps is (B, D, H, W) on every path that reaches here.
        g_x = jax.lax.index_in_dim(comps, 0, axis=1, keepdims=False)
        g_y = jax.lax.index_in_dim(comps, 1, axis=1, keepdims=False)
        orientation = jnp.arctan2(g_y, g_x)

    edges = None
    if config.hysteresis:
        from repro.core import nms

        # Post-gather by design: edge linking is a global fixpoint (a chain
        # may cross every tile/shard), so it runs on the assembled thin map
        # — identical inputs on every backend and mesh, hence identical
        # edges. Thresholds scale with the raw-magnitude peak and apply to
        # the *unnormalized* thin map (scale-invariant either way).
        low, high = nms.resolve_thresholds(peak, config.low, config.high)
        edges = nms.hysteresis(mag, low, high)

    if config.normalize:
        # The rescale expression matches the legacy pipeline op-for-op.
        mag = mag * (255.0 / jnp.maximum(peak, 1e-8))

    def unbatch(a, extra_dims=0):
        return a.reshape(batch_shape + a.shape[a.ndim - 2 - extra_dims:])

    return EdgeResult(
        magnitude=unbatch(mag),
        components=unbatch(comps, extra_dims=1)
        if config.with_components else None,
        orientation=unbatch(orientation) if config.with_orientation else None,
        peak=peak.reshape(batch_shape) if config.with_max else None,
        thin=unbatch(mag) if config.nms else None,
        edges=unbatch(edges) if config.hysteresis else None,
        layout=layout,
        config=config,
    )


# ---------------------------------------------------------------------------
# The streaming engine: per-frame delta-skip + temporal hysteresis
# ---------------------------------------------------------------------------

def stream_block_shape(
    h: int,
    w: int,
    config: "EdgeConfig",
    *,
    rgb: bool = False,
    dtype: str = "float32",
    tuning_cache: Optional[tuning.TuningCache] = None,
) -> Tuple[int, int]:
    """The (block_h, block_w) delta-tile grid for a stream of (h, w) frames.

    On the Pallas backends this IS the kernel tile (mask entries map 1:1 to
    grid steps); on XLA it only sets the change-test/splice granularity.
    Explicit config overrides win everywhere so a stream's grid is
    reproducible; otherwise Pallas consults the tuning cache and XLA takes
    the kernel's default geometry.
    """
    if config.block_h and config.block_w:
        return config.block_h, config.block_w
    backend = resolve_backend(config.backend)
    if backend == "xla":
        spec = get_operator(config.operator, config.params)
        return ekern.default_block_shape(
            h, w, spec.size, channels=3 if rgb else None
        )
    bh, bw, _depth, _src = choose_block_shape(
        h, w, operator=config.operator, variant=config.variant,
        dtype=dtype, backend=backend, padding=config.padding,
        layout="rgb" if rgb else "gray", block_h=config.block_h,
        block_w=config.block_w, cache=tuning_cache,
    )
    return bh, bw


def _block_reduce_max(x: jnp.ndarray, bh: int, bw: int) -> jnp.ndarray:
    """(B, H, W) -> (B, gh, gw) per-tile max (ragged tails are partial
    windows). Identical values to the kernel's masked block maxima because
    the magnitude is non-negative and max is exact.

    A reshape and a max, not ``reduce_window``: XLA:TPU stages a
    ``reduce_window`` over 64x256 windows in scoped VMEM and runs out of it
    at 2 x 2048^2."""
    b, h, w = x.shape
    gh, gw = -(-h // bh), -(-w // bw)
    x = jnp.pad(x, ((0, 0), (0, gh * bh - h), (0, gw * bw - w)))
    return jnp.max(x.reshape(b, gh, bh, gw, bw), axis=(2, 4))


def _block_reach(n: int, b: int, r: int) -> Tuple[int, int]:
    """(up, down) reach, in whole blocks, of the pixels a tile's valid
    outputs read along one axis of length ``n`` tiled by ``b``.

    A valid output reads only its ``r``-neighborhood (boundary rules map
    overhang back inside that neighborhood), so ``ceil(r / b)`` blocks each
    way — whatever larger aligned window the kernel DMAs, the cells outside
    the stencil are never selected.
    """
    if -(-n // b) <= 1:
        return 0, 0
    reach = -(-r // b)
    return reach, reach


def _dilate_blocks(
    changed: jnp.ndarray, reach_h: Tuple[int, int], reach_w: Tuple[int, int]
) -> jnp.ndarray:
    """OR-dilate the (B, gh, gw) change map so every tile whose input
    window can see a changed block is marked for recompute."""
    (uh, dh), (uw, dw) = reach_h, reach_w
    if uh == dh == uw == dw == 0:
        return changed
    y = jax.lax.reduce_window(
        changed.astype(jnp.int32), 0, jax.lax.max,
        (1, uh + dh + 1, uw + dw + 1), (1, 1, 1),
        ((0, 0), (uh, dh), (uw, dw)),
    )
    return y > 0


def stream_delta(
    x: jnp.ndarray,
    state: "StreamState",
    config: "EdgeConfig",
    *,
    rgb: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-tile change test of ``x`` against the cached previous frame.

    ``x``: ``(B, H, W[, 3])`` in kernel dtype (u8 compares are exact; so
    are f32 bit compares). Returns ``(changed, skipped)``: a ``(B, gh,
    gw)`` bool recompute mask — per-tile *input-window* change, i.e. the
    raw per-block diff OR-dilated by the window reach so halo reads are
    honored — and the ``(B,)`` int32 count of skippable tiles. An
    uninitialized state marks every tile changed (the caches are zeros,
    not frame -1). Fully traceable; the serve engine also calls it alone
    to host-check for the all-static fast path.
    """
    bh, bw = state.block
    h, w = (x.shape[-3], x.shape[-2]) if rgb else (x.shape[-2], x.shape[-1])
    b = x.shape[0]
    gh, gw = -(-h // bh), -(-w // bw)
    if not state.initialized:
        changed = jnp.ones((b, gh, gw), bool)
    else:
        diff = x != state.frame
        if rgb:
            diff = diff.any(axis=-1)
        blocks = _block_reduce_max(diff.astype(jnp.float32), bh, bw) > 0
        config = config.resolved()
        r_in = window_radius(
            config.plan.linear_reach if config.plan is not None
            else config.spec.radius,
            config.nms,
        )
        changed = _dilate_blocks(
            blocks, _block_reach(h, bh, r_in), _block_reach(w, bw, r_in)
        )
    skipped = jnp.int32(gh * gw) - jnp.sum(
        changed.astype(jnp.int32), axis=(-2, -1)
    )
    return changed, skipped


def _stream_epilogue(
    x, config, state, primary, bmax, skipped, *, batch_shape, layout
):
    """Shared tail of the streaming paths: peak from the (spliced) block
    maxima, plain or temporal hysteresis, normalization, result + next
    state. Runs every frame — even a fully-spliced one — because the
    temporal seed strength decays per frame and normalization/linking are
    cheap XLA stages on the assembled map."""
    from repro.api import EdgeResult, StreamState
    from repro.core import nms

    need_peak = config.normalize or config.with_max or config.hysteresis
    peak = None
    if need_peak:
        peak = jnp.max(bmax, axis=(-2, -1), keepdims=True)  # (B, 1, 1)

    edges = None
    new_seed = None
    if config.hysteresis:
        low, high = nms.resolve_thresholds(peak, config.low, config.high)
        if config.temporal:
            seeds, decayed = nms.temporal_seeds(state.seed, config.decay)
            edges = nms.hysteresis(primary, low, high, seed=seeds)
            new_seed = nms.update_seed_strength(decayed, edges)
        else:
            edges = nms.hysteresis(primary, low, high)

    mag = primary
    if config.normalize:
        mag = mag * (255.0 / jnp.maximum(peak, 1e-8))

    new_state = StreamState(
        frame=x, primary=primary, bmax=bmax, seed=new_seed,
        block=state.block, initialized=True,
    )

    def unbatch(a):
        return a.reshape(batch_shape + a.shape[-2:])

    result = EdgeResult(
        magnitude=unbatch(mag),
        peak=peak.reshape(batch_shape) if config.with_max else None,
        thin=unbatch(mag) if config.nms else None,
        edges=unbatch(edges) if config.hysteresis else None,
        skipped=skipped.reshape(batch_shape),
        layout=layout,
        config=config,
    )
    return result, new_state


def _check_stream_config(config: "EdgeConfig") -> None:
    if config.plan is not None and config.plan.pre_stages:
        # The masked streaming kernel is single-stage; a multi-stage plan
        # would need per-stage scratch inside the per-tile lax.cond, which
        # the delta-splice path does not carry. Single-operator plans
        # (gradient [+ nms]) resolve to the plain operator config and are
        # fine.
        raise ValueError(
            f"streaming runs the single-stage masked kernel; plan "
            f"{config.plan.name!r} has pre-stages and is not supported on "
            "the stream path (use edge_detect for fused multi-stage plans)"
        )
    if config.shard is not None:
        raise ValueError(
            "streaming is single-device per stream group for now; drop "
            "config.shard (batch parallelism comes from grouping streams)"
        )
    if config.with_components or config.with_orientation:
        raise ValueError(
            "streaming caches the primary map only; with_components/"
            "with_orientation are not supported on the stream path"
        )
    if config.precision == "int" or config.pipeline_depth is not None:
        # The masked streaming kernel stays on the automatic-pipelining f32
        # path: its per-tile lax.cond branches around the whole compute,
        # which a cross-step DMA ring (whose copies must be unconditional)
        # cannot coexist with, and the delta-splice caches are f32.
        # precision="auto" is fine — it resolves to f32 here.
        raise ValueError(
            "streaming runs the automatic-pipelining f32 kernel; explicit "
            "precision='int' / pipeline_depth are not supported on the "
            "stream path"
        )


def edge_stream(
    images: jnp.ndarray,
    config: "EdgeConfig",
    state: Optional["StreamState"] = None,
    *,
    layout: Optional[str] = None,
    changed: Optional[jnp.ndarray] = None,
    tuning_cache: Optional[tuning.TuningCache] = None,
) -> tuple:
    """One streaming frame step: delta-skip compute + temporal epilogue.

    ``images``: one frame per stream — ``HW``/``HWC`` or a same-resolution
    batch ``NHW``/``NHWC`` (time is the successive calls, so video-stack
    layouts are rejected). ``state`` is the previous step's
    :class:`~repro.api.StreamState` (``None`` = cold start: every tile
    recomputes and the caches fill). ``changed`` lets a caller that
    already ran :func:`stream_delta` (the serve engine's all-static host
    check) pass the mask in instead of recomputing it.

    Backend split:

      * Pallas backends run the masked-grid megakernel
        (``kernels.edge.edge_stream_pallas``): flagged tiles recompute,
        the rest branch to a cached-tile splice.
      * XLA recomputes the frame and splices per-tile with a select — the
        mask is accounting there (XLA fuses the whole frame; its real
        delta win is the engine's whole-frame short-circuit onto
        :func:`edge_stream_cached`).

    Either way the output is bit-identical to stateless full recompute
    (unchanged input windows reproduce identical arithmetic), which the
    streaming test battery pins.

    Returns ``(EdgeResult, StreamState)``; ``result.skipped`` counts the
    delta-skipped tiles per stream.
    """
    from repro.api import StreamState, detect_layout

    config = config.resolved()
    _check_stream_config(config)
    images = jnp.asarray(images)
    layout = layout or detect_layout(images.shape)
    if "T" in layout or layout.count("N") > 1:
        raise ValueError(
            "streaming takes one frame per stream per call, not a video "
            f"stack (layout {layout!r}); iterate frames through the state"
        )
    rgb = layout.endswith("C")
    backend = resolve_backend(config.backend)

    x = ekern.kernel_dtype(images)
    if rgb:
        batch_shape = x.shape[:-3]
        h, w = x.shape[-3], x.shape[-2]
        x = x.reshape((-1, h, w, 3))
    else:
        batch_shape = x.shape[:-2]
        h, w = x.shape[-2], x.shape[-1]
        x = x.reshape((-1, h, w))

    if state is None:
        state = StreamState.init(
            x.shape[0], h, w, config, rgb=rgb, dtype=x.dtype
        )
    bh, bw = state.block
    if state.frame.shape != x.shape:
        raise ValueError(
            f"stream state was built for frames {state.frame.shape}, got "
            f"{x.shape}; streams of different shape need their own state"
        )

    if changed is None:
        changed, skipped = stream_delta(x, state, config, rgb=rgb)
    else:
        gh, gw = state.grid
        skipped = jnp.int32(gh * gw) - jnp.sum(
            changed.astype(jnp.int32), axis=(-2, -1)
        )

    if backend == "xla":
        run = _backend_compute(
            config, backend, rgb=rgb, need_comps=False,
            need_raw=config.nms, block_h=None, block_w=None,
        )
        fresh, _comps, raw = run(x)
        fresh_bmax = _block_reduce_max(raw if raw is not None else fresh,
                                       bh, bw)
        pixel_mask = jnp.repeat(
            jnp.repeat(changed, bh, axis=-2), bw, axis=-1
        )[:, :h, :w]
        primary = jnp.where(pixel_mask, fresh, state.primary)
        bmax = jnp.where(changed, fresh_bmax, state.bmax)
    else:
        primary, bmax = ekern.edge_stream_pallas(
            x, state.primary, state.bmax, changed.astype(jnp.int32),
            operator=config.operator, variant=config.variant,
            params=config.params, directions=config.directions,
            padding=config.padding, block_h=bh, block_w=bw, rgb=rgb,
            out_nms=config.nms, interpret=(backend == "pallas-interpret"),
        )

    return _stream_epilogue(
        x, config, state, primary, bmax, skipped,
        batch_shape=batch_shape, layout=layout,
    )


def edge_stream_cached(
    config: "EdgeConfig",
    state: "StreamState",
    *,
    layout: str = "NHW",
) -> tuple:
    """The all-static fast path: a frame step with no frame compute.

    When the serve engine's host-side check of :func:`stream_delta` shows
    zero changed tiles across the whole group, the kernel launch (and even
    the frame's HBM read) is skipped outright — the cached primary map and
    block maxima ARE this frame's outputs. Only the epilogue runs, because
    it still must: the temporal seed strength decays every frame (edges
    can disappear on a static scene as their seeds expire) and
    normalization/linking read the cached values. Bit-identical to
    :func:`edge_stream` on the same static frame.
    """
    config = config.resolved()
    _check_stream_config(config)
    if not state.initialized:
        raise ValueError(
            "edge_stream_cached needs an initialized state (run at least "
            "one edge_stream step first)"
        )
    batch_shape = () if layout in ("HW", "HWC") else state.primary.shape[:1]
    skipped = jnp.full(state.primary.shape[0], state.tiles, jnp.int32)
    return _stream_epilogue(
        state.frame, config, state, state.primary, state.bmax, skipped,
        batch_shape=batch_shape, layout=layout,
    )
