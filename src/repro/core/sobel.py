"""Multi-directional Sobel operator — the paper's variant ladder in pure JAX.

Variants (mirroring paper Table 1):
  * ``direct``    — dense 2-D correlation per direction (the "GM"/OpenCV
                    baseline: 4 x 25 MACs per output pixel).
  * ``separable`` — "RG": K_x / K_y computed via their separable factors
                    (Eq. 5-7); K_d / K_dt still dense 2-D.
  * ``v1``        — "RG-v1": diagonal transform K_d+- = K_d +- K_dt (Eq. 10-17);
                    K_d+ exploits odd row symmetry (F_k3 = -F_k1, F_k4 = -F_k0),
                    K_d- exploits even row symmetry (3 distinct row passes).
  * ``v2``        — "RG-v2": K_d- further split into two separable outer
                    products (Eq. 18-19); the first reuses K_x's horizontal
                    pass F verbatim, the second is a 2-tap difference D.

All variants are mathematically identical (integer weights -> bit-exact in
float32); tests assert exact agreement.  Inputs may carry arbitrary leading
batch dims: shape ``(..., H, W)``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import filters as F
from repro.core.filters import SobelParams

__all__ = [
    "sobel",
    "sobel_components",
    "spec_components",
    "plan_components",
    "magnitude",
    "VARIANTS",
]

VARIANTS = ("direct", "separable", "v1", "v2")


# ---------------------------------------------------------------------------
# 1-D pass helpers (shifted-slice formulation — the TPU analogue of the
# paper's register taps; XLA fuses these into single vectorized expressions)
# ---------------------------------------------------------------------------

# Most negative finite f32. ``maximum(t, _F32_LOWEST)`` is an exact identity
# for every finite t that the XLA algebraic simplifier cannot fold (only the
# true identity element -inf is folded), so a tap product wrapped in it can
# never be contracted into an FMA with the accumulating add. Integer-valued
# images don't need this (their tap products are exact either way), but the
# fused RGB megakernel feeds non-integer luma values through these passes,
# and eager, jit, and Pallas executions must round identically for the
# repo's bit-exactness contract (same reasoning as ``magnitude`` below).
_F32_LOWEST = float(np.finfo(np.float32).min)


def _tap(term: jnp.ndarray, w: float) -> jnp.ndarray:
    """``w * term`` with FMA contraction blocked (±1 taps skip the mul).

    Integer-dtype terms (the exact low-precision lane: u8 frames × integer
    taps accumulated in i16/i32, see ``repro.core.ladder``) multiply
    plainly — integer mul-add is exact, there is no FMA rounding hazard to
    fence, and the fence constant is a float anyway.
    """
    if w == 1.0:
        return term
    if w == -1.0:
        return -term
    if jnp.issubdtype(term.dtype, jnp.integer):
        if w != int(w):
            raise ValueError(
                f"fractional tap {w!r} reached the integer lane; "
                "repro.core.ladder.int_lane_eligible should have gated this"
            )
        return term * jnp.asarray(int(w), term.dtype)
    return jnp.maximum(w * term, jnp.float32(_F32_LOWEST))


def _halve(x: jnp.ndarray) -> jnp.ndarray:
    """Exact ``x / 2`` for the operator transform's even-valued sums.

    ``gd_plus ± gd_minus`` is ``2 * (kd ⊛ x)`` / ``2 * (kdt ⊛ x)`` by
    construction (Eq. 10-11), i.e. always even in the integer lane — so an
    arithmetic right shift is exact there (even negatives shift exactly;
    the floor-vs-truncate discrepancy only exists for odd negatives, which
    cannot occur). The float lane keeps the historical ``* 0.5`` (exact:
    scaling by a power of two).
    """
    if jnp.issubdtype(x.dtype, jnp.integer):
        return x >> 1
    return x * 0.5


def _hpass(x: jnp.ndarray, taps: np.ndarray, out_w: int) -> jnp.ndarray:
    """Horizontal correlation: out[..., y, j] = sum_t taps[t] * x[..., y, j+t].

    Static zero taps are skipped (the paper's F pass is 4 MACs, D is 2).
    """
    acc = None
    for t, w in enumerate(np.asarray(taps).tolist()):
        if w == 0.0:
            continue
        # lax.slice_in_dim, not x[..., :, t:t+out_w]: the mixed
        # Ellipsis/colon form lowers to a gather, which Mosaic can't compile
        # inside the Pallas kernels (a static slice is also faster on XLA).
        term = _tap(jax.lax.slice_in_dim(x, t, t + out_w, axis=-1), w)
        acc = term if acc is None else acc + term
    if acc is None:
        return jnp.zeros(x.shape[:-1] + (out_w,), x.dtype)
    return acc


def _vpass(x: jnp.ndarray, taps: np.ndarray, out_h: int) -> jnp.ndarray:
    """Vertical correlation: out[..., i, x] = sum_t taps[t] * x[..., i+t, x]."""
    acc = None
    for t, w in enumerate(np.asarray(taps).tolist()):
        if w == 0.0:
            continue
        term = _tap(jax.lax.slice_in_dim(x, t, t + out_h, axis=-2), w)
        acc = term if acc is None else acc + term
    if acc is None:
        return jnp.zeros(x.shape[:-2] + (out_h,) + x.shape[-1:], x.dtype)
    return acc


def _correlate2d(x: jnp.ndarray, kernel: np.ndarray, out_h: int, out_w: int) -> jnp.ndarray:
    """Dense 2-D correlation via shifted slices (valid region)."""
    kh, kw = kernel.shape
    acc = None
    for i in range(kh):
        for j in range(kw):
            w = float(kernel[i, j])
            if w == 0.0:
                continue
            term = jax.lax.slice_in_dim(
                jax.lax.slice_in_dim(x, i, i + out_h, axis=-2),
                j, j + out_w, axis=-1,
            )
            term = _tap(term, w)
            acc = term if acc is None else acc + term
    assert acc is not None
    return acc


# ---------------------------------------------------------------------------
# Spec-driven variant ladder (operates on a pre-padded image or a halo'd
# Pallas tile; returns the direction components, each of shape (..., H, W)).
# This single implementation is shared by the pure-XLA path AND the kernel
# body of ``repro.kernels.edge`` — cross-backend bit-exactness by
# construction.
# ---------------------------------------------------------------------------

def _sym_rowpass(xp, dense: np.ndarray, h, w):
    """Dense correlation exploiting shared/negated rows (Eqs. 13-17).

    One horizontal pass per *distinct* row vector: rows equal to an earlier
    row reuse its pass, rows equal to its negation reuse it with a subtract.
    For K_d+ (odd row symmetry ``[k0, k1, 0, -k1, -k0]``) and K_d- (even,
    ``[r0, r1, r2, r1, r0]``) this reproduces the paper's row-pass structure
    — and the exact accumulation order of the pre-registry implementation.
    """
    dense = np.asarray(dense, np.float32)
    passes = {}
    acc = None
    for i, r_ in enumerate(dense):
        if not np.any(r_):
            continue
        key, nkey = tuple(r_.tolist()), tuple((-r_).tolist())
        if key in passes:
            f, sign = passes[key], 1.0
        elif nkey in passes:
            f, sign = passes[nkey], -1.0
        else:
            f, sign = passes.setdefault(key, _hpass(xp, r_, w)), 1.0
        term = jax.lax.slice_in_dim(f, i, i + h, axis=-2)
        if acc is None:
            acc = term if sign > 0 else -term
        else:
            acc = acc + term if sign > 0 else acc - term
    assert acc is not None
    return acc


def spec_components(
    xp, spec: F.OperatorSpec, h, w, variant: str, directions: int, *, sink=None
):
    """Direction components of ``spec`` on the pre-padded image ``xp``.

    ``variant``/``directions`` must already be resolved against the spec
    (``spec.resolve_variant`` / ``spec.resolve_directions``).

    The arithmetic runs in ``xp.dtype``: float input takes the historical
    fenced-f32 path; integer input (the exact low-precision lane — u8
    frames cast to the i16/i32 budget ``repro.core.ladder`` proves) runs
    plain integer mul-add, bit-identical to the f32 lane because both
    compute the same exact integers.

    ``sink`` (optional ``sink(name, array) -> array``) is applied to the
    named separable row-pass intermediates — ``"f"``/``"s"`` (Eq. 5-7's
    horizontal passes) and v2's 2-tap difference ``"d"`` — before their
    column passes consume them. The fused Pallas kernel's DMA-pipelined
    path uses it to spill each row pass into a dedicated VMEM scratch
    buffer and read it back (deterministic VMEM residency for the reused
    factors); a sink must return its input's values unchanged, so the
    default identity and any store/load round-trip are bit-identical.
    """
    if sink is None:
        def sink(_name, arr):
            return arr
    if variant == "direct":
        return tuple(_correlate2d(xp, k, h, w) for k in spec.bank(directions))

    # Separable x/y (Eq. 5-7): one horizontal pass each, columns include the
    # leading factor a.
    col_x, row_x = spec.sep_factors(0)
    col_y, row_y = spec.sep_factors(1)
    f = sink("f", _hpass(xp, row_x, w))  # the reused F pass (4 MACs: zero centre)
    s = sink("s", _hpass(xp, row_y, w))
    gx = _vpass(f, col_x, h)
    gy = _vpass(s, col_y, h)
    if directions == 2:
        return (gx, gy)

    if variant == "separable":
        bank = spec.bank(4)
        gd = _correlate2d(xp, bank[2], h, w)
        gdt = _correlate2d(xp, bank[3], h, w)
        return (gx, gy, gd, gdt)

    # RG-v1/v2: the ± operator transformation (Eq. 10-19).
    gd_plus = _sym_rowpass(xp, spec.kd_plus_dense(), h, w)
    if variant == "v1":
        gd_minus = _sym_rowpass(xp, spec.kd_minus_dense(), h, w)
    elif variant == "v2":
        col_f, col_d, row_d = spec.v2_arrays()
        d = sink("d", _hpass(xp, row_d, w))  # 2-tap difference D = p3 - p1
        gd_minus = _vpass(f, col_f, h) - _vpass(d, col_d, h)
    else:
        raise ValueError(f"unknown variant {variant!r}; expected one of {VARIANTS}")
    gd = _halve(gd_plus + gd_minus)   # Eq. 11 (sums are even: exact either lane)
    gdt = _halve(gd_plus - gd_minus)
    return (gx, gy, gd, gdt)


# ---------------------------------------------------------------------------
# StencilPlan chaining: single-plane pre-stages on shrinking extents, then
# the gradient stage via the variant ladder above. Shared — like
# ``spec_components`` — by the XLA reference path and the fused Pallas
# kernel body, so fused-vs-staged bit-exactness holds by construction.
# ---------------------------------------------------------------------------

def _window_reduce(x, r: int, mode: str, out_h, out_w):
    """Separable ``(2r+1)``-square max/min (morphological dilate/erode).

    max/min over a square window separates exactly into a horizontal then a
    vertical pass of shifted-slice reductions — every output is one of the
    input values (no arithmetic), so the reduction is exact in every lane
    and every backend orders it identically.
    """
    op = jnp.maximum if mode == "max" else jnp.minimum
    acc = None
    for t in range(2 * r + 1):
        s = jax.lax.slice_in_dim(x, t, t + out_w, axis=-1)
        acc = s if acc is None else op(acc, s)
    x = acc
    acc = None
    for t in range(2 * r + 1):
        s = jax.lax.slice_in_dim(x, t, t + out_h, axis=-2)
        acc = s if acc is None else op(acc, s)
    return acc


def _stage_apply(x, stage, out_h, out_w):
    """Apply one single-plane stage to ``x`` (extent ``out + 2*radius``)."""
    if stage.kind == "linear":
        spec = stage.operator
        fac = spec.sep_factors(0)
        if fac is not None:
            col, row = fac
            return _vpass(_hpass(x, row, out_w), col, out_h)
        return _correlate2d(x, spec.bank(1)[0], out_h, out_w)
    if stage.kind == "window_reduce":
        return _window_reduce(x, stage.radius, stage.op, out_h, out_w)
    if stage.kind == "pointwise":
        fn, _bound = F.get_pointwise(stage.op)
        return fn(x)
    raise ValueError(f"stage {stage.name!r} (kind {stage.kind!r}) is not a "
                     "single-plane stage")


def plan_components(ext, plan, h, w, variant: str, directions: int, *,
                    sink=None, stage_sink=None):
    """Direction components of ``plan`` on ``ext``, the input extended by
    ``plan.linear_reach`` on each side (``(h + 2R, w + 2R)``).

    Each pre-stage consumes its own radius off the margin — stage ``k``'s
    output extent is ``h + 2 * (remaining radii)`` — so after the last
    pre-stage the plane is extended by exactly the gradient's radius, and
    the existing :func:`spec_components` ladder finishes the chain. This
    pad-once / shrink-per-stage walk is *the same arithmetic* as running
    each stage separately with its own (remaining-reach) pad: correlation
    at an interior point only reads values the larger pad also contains.

    ``variant``/``directions`` apply to the gradient stage; plans without
    a gradient return the single smoothed plane as a 1-tuple.

    ``sink`` forwards to :func:`spec_components` (the gradient row-pass
    spill); ``stage_sink`` (optional ``stage_sink(idx, array) -> array``)
    is applied to each pre-stage's output plane — the fused kernel's
    DMA-pipelined path spills the inter-stage planes into dedicated VMEM
    scratch. A stage_sink must return its input's values unchanged, so
    the identity default and a store/load round-trip are bit-identical.
    """
    cur = ext
    remaining = plan.linear_reach
    for idx, stage in enumerate(plan.pre_stages):
        remaining -= stage.radius
        cur = _stage_apply(cur, stage, h + 2 * remaining, w + 2 * remaining)
        if stage_sink is not None:
            cur = stage_sink(idx, cur)
    spec = plan.gradient
    if spec is None:
        return (cur,)
    return spec_components(cur, spec, h, w, variant, directions, sink=sink)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _pad(image: jnp.ndarray, r: int, padding: str) -> Tuple[jnp.ndarray, int, int]:
    h, w = image.shape[-2], image.shape[-1]
    if padding == "valid":
        return image, h - 2 * r, w - 2 * r
    pad_widths = [(0, 0)] * (image.ndim - 2) + [(r, r), (r, r)]
    mode = {"reflect": "reflect", "edge": "edge", "zero": "constant"}[padding]
    return jnp.pad(image, pad_widths, mode=mode), h, w


def sobel_components(
    image: jnp.ndarray,
    *,
    size: int = 5,
    directions: int = 0,
    variant: str = "v2",
    params: SobelParams = SobelParams(),
    padding: str = "reflect",
    operator: "str | None" = None,
    precision: str = "f32",
    plan=None,
) -> Tuple[jnp.ndarray, ...]:
    """Per-direction gradient images ``(G_x, G_y[, G_d, G_dt])``.

    ``operator`` selects any registered :class:`~repro.core.filters.OperatorSpec`
    by name (``sobel5``/``sobel3``/``scharr3``/``prewitt3``/``sobel7``/...);
    when omitted, the legacy ``size`` kwarg picks the Sobel operator of that
    size. ``directions`` of 0 means the operator's maximum.

    ``plan`` (a :class:`~repro.core.filters.StencilPlan` or registered plan
    name) chains the plan's single-plane pre-stages ahead of its gradient
    stage with one composed pad of ``plan.linear_reach`` — the staged
    semantics of :func:`plan_components`. It overrides
    ``operator``/``size``; the plan must carry a gradient stage (this
    function returns direction components).

    ``precision="int"`` runs the exact low-precision lane: uint8/uint16 input
    cast to the i16/i32 budget proved by ``repro.core.ladder``, gradients
    accumulated in integers, components cast to f32 on return —
    bit-identical to the default f32 lane (both compute the same exact
    integers). Raises for inputs/operators the budget does not cover.
    """
    if variant not in VARIANTS and variant != "auto":
        raise ValueError(f"unknown variant {variant!r}; expected one of {VARIANTS}")
    if precision not in ("f32", "int"):
        raise ValueError(f"unknown precision {precision!r}; expected 'f32' or 'int'")
    if plan is not None:
        plan = F.resolve_plan(plan)
        spec = plan.gradient
        if spec is None:
            raise ValueError(
                f"plan {plan.name!r} has no gradient stage; "
                "sobel_components returns direction components"
            )
        reach = plan.linear_reach
    else:
        spec = F.get_operator(operator or F.operator_for_size(size), params)
        reach = spec.radius
    directions = spec.resolve_directions(directions)
    variant = spec.resolve_variant(variant)
    if precision == "int":
        from repro.core import ladder

        if plan is not None:
            ok, reason = ladder.plan_int_eligible(
                plan, rgb=False, input_dtype=image.dtype
            )
            acc = ladder.plan_accum_dtype(plan, input_dtype=image.dtype)
        else:
            ok, reason = ladder.int_lane_eligible(
                spec, rgb=False, input_dtype=image.dtype
            )
            acc = ladder.accum_dtype(spec, input_dtype=image.dtype)
        if not ok:
            raise ValueError(f"precision='int' unavailable: {reason}")
        x = image.astype(jnp.dtype(acc))
    else:
        x = image.astype(jnp.float32)
    xp, h, w = _pad(x, reach, padding)
    if plan is not None:
        comps = plan_components(xp, plan, h, w, variant, directions)
    else:
        comps = spec_components(xp, spec, h, w, variant, directions)
    if precision == "int":
        comps = tuple(c.astype(jnp.float32) for c in comps)
    return comps


def magnitude(components: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """Root-sum-of-squares aggregation (Eq. 2 / Eq. 4).

    Each square is clamped through ``maximum(g*g, 0)`` — an exact identity
    for squares — so codegen cannot contract the multiply into an FMA with
    the accumulating add (``lax.optimization_barrier`` does not survive to
    XLA:CPU codegen). Every execution mode (eager, jit, Pallas interpret,
    Pallas TPU) then rounds ``g*g`` identically, which — together with the
    exactness of the integer-weight taps in f32 — makes kernel-vs-core
    outputs bit-exact, not just allclose.
    """
    acc = None
    for g in components:
        g2 = jnp.maximum(g * g, jnp.float32(0.0))
        acc = g2 if acc is None else acc + g2
    return jnp.sqrt(acc)


def sobel(
    image: jnp.ndarray,
    *,
    size: int = 5,
    directions: int = 0,
    variant: str = "v2",
    params: SobelParams = SobelParams(),
    padding: str = "reflect",
    return_components: bool = False,
    operator: "str | None" = None,
    precision: str = "f32",
):
    """Multi-directional edge magnitude ``G`` (paper Eq. 4).

    Args:
      image: ``(..., H, W)`` grayscale image(s); any real dtype.
      size: 3 or 5 (legacy operator selector; ignored when ``operator`` set).
      directions: 2 (``G_x, G_y``) or 4 (+ ``G_d, G_dt``); 0 (default) =
        the operator's maximum (4 for the Sobel 3x3/5x5 family).
      variant: one of ``direct | separable | v1 | v2`` (identical results;
        coerced to the operator's best supported variant).
      params: generalized weights (paper §3.2; Sobel-5x5 family only).
      padding: ``reflect | edge | zero`` (same-size output) or ``valid``.
      return_components: also return the per-direction gradients.
      operator: registered operator name (overrides ``size``).
      precision: ``f32`` (default) or ``int`` — the exact integer lane
        (see :func:`sobel_components`); magnitude is always f32.
    """
    comps = sobel_components(
        image,
        size=size,
        directions=directions,
        variant=variant,
        params=params,
        padding=padding,
        operator=operator,
        precision=precision,
    )
    g = magnitude(comps)
    if return_components:
        return g, comps
    return g


sobel_jit = jax.jit(
    sobel,
    static_argnames=(
        "size",
        "directions",
        "variant",
        "params",
        "padding",
        "return_components",
        "operator",
        "precision",
    ),
)
