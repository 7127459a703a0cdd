"""Exact integer-accumulation budgets for the low-precision tap lane.

The paper's "operator transformation" trick restructures the taps to cut
arithmetic; the orthogonal precision trick is that a u8 or u16 frame
correlated with *integer* taps never needs floating point at all: every
intermediate the variant ladder materializes is an exact integer bounded by
``input_max * sum(|taps|)`` (``input_max`` the frame dtype's largest value,
:func:`frame_max`), so the whole gradient stage can run in
i16/i32 and convert to f32 only at the magnitude/NMS boundary — and the
result is *bit-identical* to the f32 lane, because both lanes compute the
same exact integers (f32 holds every integer up to 2^24 exactly).

This module is the single source of those budgets. It is shared by:

  * the static analyzer (``repro.analysis.rules`` DTYPE001), which proves
    the budget per registered operator and — since the integer lane landed
    — checks the traced kernel's *actual* accumulation dtype against it;
  * the dispatcher (``repro.kernels.dispatch.resolve_precision``), which
    gates ``EdgeConfig.precision="auto"|"int"`` on the same proof;
  * the kernels (``repro.kernels.edge``), which pick the accumulation
    dtype from :func:`accum_dtype`.

Bound derivation (why ``worst`` is what it is): per direction the final
response is ``sum_t taps[t] * x[t]`` with ``0 <= x <= input_max``, so
``|response| <= input_max * sum|taps|``. Partial sums and the separable
row/column passes are bounded by the same triangle inequality (a partial
sum omits terms; a row-pass intermediate times a column tap is one term
of the dense expansion). The v1/v2 operator transform additionally forms
``gd_plus = gd + gdt`` and ``gd_minus = gd - gdt`` (Eq. 10-11), so for
4-direction banks the binding bound is the *pairwise* one — the two
largest per-direction bounds added. The halving in ``gd = (gd_plus +
gd_minus) / 2`` is exact in integers because the sum is ``2 * gd`` (even
by construction); the kernels spell it as an arithmetic right shift.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

__all__ = [
    "F32_EXACT_INT",
    "INT_LANE_INPUTS",
    "frame_max",
    "tap_accumulation_bounds",
    "accum_dtype",
    "int_lane_eligible",
    "plan_input_bound",
    "plan_int_eligible",
    "plan_accum_dtype",
]

# Exact-representation ceilings for the dtype ladder.
F32_EXACT_INT = 2**24
_I16_MAX = 2**15 - 1
_I32_MAX = 2**31 - 1

# The frame dtypes the integer lane takes, each bounded by its largest
# value: 8-bit video, and 12/16-bit sensor frames in 16-bit containers.
INT_LANE_INPUTS = (np.dtype(np.uint8), np.dtype(np.uint16))


def frame_max(input_dtype) -> Optional[int]:
    """The largest value of an ``input_dtype`` frame, the bound every proof
    here starts from; None for a dtype the integer lane does not take."""
    dt = np.dtype(input_dtype)
    return int(np.iinfo(dt).max) if dt in INT_LANE_INPUTS else None


def _input_gate(input_dtype) -> Tuple[Optional[int], str]:
    """(input bound, reason): the first gate of both eligibility checks."""
    m = frame_max(input_dtype)
    if m is None:
        names = "/".join(d.name for d in INT_LANE_INPUTS)
        return None, (
            f"input dtype {np.dtype(input_dtype).name} is not {names} — the "
            "integer bound only covers unsigned 8/16-bit frames"
        )
    return m, ""


def tap_accumulation_bounds(spec, *, input_max: int = 255) -> Dict[str, object]:
    """Worst-case accumulation magnitude of ``input_max``-bounded input
    against the spec's dense filter bank.

    Per direction the bound is ``input_max * sum(|taps|)``; for
    4-direction operators the v2 operator-transform path combines two
    directional kernels (kd ± kdᵀ), so the pairwise bound — the two
    largest per-direction sums added — covers every intermediate either
    variant materializes. Gradients only: the NMS magnitude stays f32 by
    contract and is not part of the integer ladder.
    """
    bank = spec.bank(max(spec.directions))
    integer = bool(np.all(bank == np.round(bank)))
    per_dir = [float(input_max * np.abs(k).sum()) for k in bank]
    worst = max(per_dir)
    if len(per_dir) >= 4:
        worst = sum(sorted(per_dir)[-2:])
    return {
        "integer_taps": integer,
        "per_direction": per_dir,
        "worst": worst,
        "fits_i16": worst <= _I16_MAX,
        "fits_i32": worst <= _I32_MAX,
        "f32_exact": worst <= F32_EXACT_INT,
    }


def accum_dtype(spec, *, input_dtype="uint8") -> Optional[str]:
    """Narrowest exact integer accumulation dtype for the spec, or None.

    Returns ``"int16"``/``"int32"`` when the integer lane is provably
    bit-exact against the f32 lane for ``input_dtype`` frames (bounded by
    :func:`frame_max`: 255 for u8, 65535 for u16), else ``None``. Three
    conditions, all from the same
    :func:`tap_accumulation_bounds` computation DTYPE001 checks:

      * integer taps — fractional taps have no exact integer form;
      * the bound fits the candidate integer dtype (no wraparound);
      * the bound fits f32's exact-integer range (≤ 2^24) — without this
        the *f32* lane itself rounds, so "bit-identical by construction"
        would have nothing exact to be identical to.
    """
    m = frame_max(input_dtype)
    if m is None:
        return None
    return _narrowest(tap_accumulation_bounds(spec, input_max=m))


def _narrowest(b) -> Optional[str]:
    if not b["integer_taps"] or not b["f32_exact"]:
        return None
    if b["fits_i16"]:
        return "int16"
    if b["fits_i32"]:
        return "int32"
    return None


def int_lane_eligible(spec, *, rgb: bool, input_dtype="uint8") -> Tuple[bool, str]:
    """(eligible, reason) for running the exact integer lane.

    ``reason`` explains the *first* failing gate when ineligible (used
    verbatim in the ``precision="int"`` error message). RGB input is
    ineligible by design: the BT.601 luma weights are fractional, and the
    f32 reference computes ``0.299*R + 0.587*G + 0.114*B`` with fenced f32
    roundings that no fixed-point formulation reproduces bit-for-bit
    (DESIGN.md §11 derives the 16-bit fixed-point luma and shows where it
    diverges) — so an integer lane on RGB could be fast but never exact.
    """
    if rgb:
        return False, (
            "RGB input needs the fractional BT.601 luma, whose fenced f32 "
            "rounding has no bit-exact fixed-point equivalent"
        )
    m, reason = _input_gate(input_dtype)
    if m is None:
        return False, reason
    b = tap_accumulation_bounds(spec, input_max=m)
    if not b["integer_taps"]:
        return False, f"operator {spec.name!r} has fractional taps"
    if not b["f32_exact"]:
        return False, (
            f"accumulation bound {b['worst']:.0f} exceeds f32's exact "
            "integer range (2^24); the f32 lane itself rounds"
        )
    if not b["fits_i32"]:
        return False, (
            f"accumulation bound {b['worst']:.0f} exceeds i32"
        )
    return True, ""


# ---------------------------------------------------------------------------
# StencilPlan extension: chain the bound through every pre-stage, then
# apply the per-operator proof above to the gradient stage with the
# chained input bound. A one-gradient-stage plan reduces exactly to
# ``int_lane_eligible(spec)``.
# ---------------------------------------------------------------------------

def plan_input_bound(plan, *, input_max: int = 255):
    """(bound, reason) — the gradient stage's input magnitude bound after
    the plan's pre-stages, or (None, reason) when a pre-stage leaves the
    integer lane. ``reason`` names the failing gate (used verbatim in the
    ``precision="int"`` error message).

    Per stage kind: window max/min selects an input value (bound
    preserved); an integer-tap linear stage multiplies the bound by
    ``sum|taps|`` (triangle inequality, same as the gradient proof); a
    fractional-tap stage (the normalized Gaussians) has no exact integer
    form; pointwise fns carry their own registered bound transform.
    """
    from repro.core import filters as F

    m = float(input_max)
    for stage in plan.pre_stages:
        if stage.kind == "window_reduce":
            continue
        if stage.kind == "linear":
            bank = stage.operator.bank(1)
            if not np.all(bank == np.round(bank)):
                return None, (
                    f"plan gate 'integer-taps': stage {stage.name!r} has "
                    "fractional taps (no exact integer form)"
                )
            m = m * float(np.abs(bank[0]).sum())
        elif stage.kind == "pointwise":
            _fn, bound = F.get_pointwise(stage.op)
            if bound is None:
                return None, (
                    f"plan gate 'integer-taps': pointwise stage "
                    f"{stage.name!r} has no integer bound transform"
                )
            m = float(bound(m))
        if m > F32_EXACT_INT:
            return None, (
                f"plan gate 'integer-taps': bound {m:.0f} after stage "
                f"{stage.name!r} exceeds f32's exact integer range (2^24)"
            )
    return m, ""


def plan_int_eligible(plan, *, rgb: bool, input_dtype="uint8") -> Tuple[bool, str]:
    """Plan-level (eligible, reason) for the exact integer lane."""
    spec = plan.gradient
    if spec is None:
        return False, (
            f"plan {plan.name!r} has no gradient stage; the integer lane "
            "covers gradient plans only"
        )
    if not plan.pre_stages:
        return int_lane_eligible(spec, rgb=rgb, input_dtype=input_dtype)
    if rgb:
        return False, (
            "RGB input needs the fractional BT.601 luma, whose fenced f32 "
            "rounding has no bit-exact fixed-point equivalent"
        )
    m, reason = _input_gate(input_dtype)
    if m is None:
        return False, reason
    m, reason = plan_input_bound(plan, input_max=m)
    if m is None:
        return False, reason
    b = tap_accumulation_bounds(spec, input_max=m)
    if not b["integer_taps"]:
        return False, f"operator {spec.name!r} has fractional taps"
    if not b["f32_exact"]:
        return False, (
            f"accumulation bound {b['worst']:.0f} exceeds f32's exact "
            "integer range (2^24); the f32 lane itself rounds"
        )
    if not b["fits_i32"]:
        return False, f"accumulation bound {b['worst']:.0f} exceeds i32"
    return True, ""


def plan_accum_dtype(plan, *, input_dtype="uint8") -> Optional[str]:
    """Narrowest exact integer accumulation dtype for the whole plan."""
    spec = plan.gradient
    if spec is None:
        return None
    if not plan.pre_stages:
        return accum_dtype(spec, input_dtype=input_dtype)
    m = frame_max(input_dtype)
    if m is None:
        return None
    m, _reason = plan_input_bound(plan, input_max=m)
    if m is None:
        return None
    return _narrowest(tap_accumulation_bounds(spec, input_max=m))
