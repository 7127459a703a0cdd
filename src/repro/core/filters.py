"""Generalized multi-directional edge filters + the declarative operator registry.

Paper §3.1–§3.2 (Eqs. 3, 5, 10, 18): all 5x5 filters are parameterized by
``SobelParams(a, b, m, n)``; the paper's (and OpenCV's) weights correspond to
``a=1, b=2, m=6, n=4``.

Orientation convention: filters are applied as *correlation* (OpenCV
``filter2D`` semantics), i.e. ``G[y, x] = sum_{i,j} K[i, j] * I[y+i-r, x+j-r]``.
This matches the paper's row-indexed aggregation equations (Eq. 7, 13, 17),
where vector ``k_i`` is applied to input row ``v - r + i``.

The registry part: every operator the stack can run — Sobel 3x3/5x5, Scharr,
Prewitt, the extended 7x7 Sobel (Bogdan et al., 2019), and anything a user
registers — is one :class:`OperatorSpec`: a frozen, hashable declaration of
its dense taps, separable factors, supported direction counts, and (where
the paper's operator-transformation decomposition applies) the K_d± data
that unlocks the RG-v1/RG-v2 variants. ``repro.core.sobel``, the Pallas
megakernel (``repro.kernels.edge``), dispatch, and the tuning cache all
consume specs — no layer hardcodes taps.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SobelParams",
    "OperatorSpec",
    "register_operator",
    "get_operator",
    "list_operators",
    "operator_for_size",
    "make_separable_spec",
    "Stage",
    "StencilPlan",
    "linear_stage",
    "pointwise_stage",
    "window_stage",
    "register_stage",
    "get_stage",
    "list_stages",
    "register_pointwise",
    "get_pointwise",
    "make_plan",
    "register_plan",
    "get_plan",
    "list_plans",
    "resolve_plan",
    "plan_identity",
    "kx",
    "ky",
    "kd",
    "kdt",
    "kd_plus",
    "kd_minus",
    "kx_factors",
    "ky_factors",
    "kd_plus_rows",
    "kd_minus_factors",
    "filter_bank_5x5",
    "filter_bank_3x3",
    "SOBEL3_GX",
    "SOBEL3_GY",
    "SOBEL3_GD",
    "SOBEL3_GDT",
]


@dataclasses.dataclass(frozen=True)
class SobelParams:
    """Generalized 5x5 Sobel weights (paper Eq. 5). Defaults = OpenCV weights."""

    a: float = 1.0
    b: float = 2.0
    m: float = 6.0
    n: float = 4.0

    def as_tuple(self) -> Tuple[float, float, float, float]:
        return (self.a, self.b, self.m, self.n)


def _arr(x) -> np.ndarray:
    return np.asarray(x, dtype=np.float32)


# ---------------------------------------------------------------------------
# Separable factors (the mathematical heart of the paper's optimization)
# ---------------------------------------------------------------------------

def kx_factors(p: SobelParams = SobelParams()):
    """K_x = a * col([1,n,m,n,1]) x row([-1,-b,0,b,1])  (Eq. 5)."""
    col = _arr([1.0, p.n, p.m, p.n, 1.0])
    row = _arr([-1.0, -p.b, 0.0, p.b, 1.0])
    return p.a, col, row


def ky_factors(p: SobelParams = SobelParams()):
    """K_y = a * col([-1,-b,0,b,1]) x row([1,n,m,n,1])  (Eq. 5)."""
    col = _arr([-1.0, -p.b, 0.0, p.b, 1.0])
    row = _arr([1.0, p.n, p.m, p.n, 1.0])
    return p.a, col, row


def kd_plus_rows(p: SobelParams = SobelParams()):
    """The two independent row vectors of K_d+ (Eq. 10/12).

    K_d+ rows are ``[k0, k1, 0, -k1, -k0]`` (odd symmetry, Eq. 14), so the
    whole filter is described by k0 and k1.  The returned vectors *include*
    the leading factor ``a``.
    """
    a, b, m, n = p.as_tuple()
    k0 = _arr([-m, -(n + b), -2.0, -(n + b), -m]) * a
    k1 = _arr([b - n, -m * b, -2.0 * n * b, -m * b, b - n]) * a
    return k0, k1


def kd_minus_factors(p: SobelParams = SobelParams()):
    """Eq. 18: K_d- = a*(colF x rowF  -  colD x rowD).

    ``rowF = [-1,-b,0,b,1]`` is **identical to K_x's row vector**, so its
    horizontal pass F is reused verbatim (RG-v2's key reuse).
    ``rowD = [0,-1,0,1,0]`` is a 2-tap difference D = p[3] - p[1].
    Returned columns include the factor ``a``.
    """
    a, b, m, n = p.as_tuple()
    col_f = _arr([m, n + b, 2.0, n + b, m]) * a
    row_f = _arr([-1.0, -b, 0.0, b, 1.0])
    col_d = _arr(
        [
            m * b + b - n,
            n * b + b * b - m * b,
            2.0 * b - 2.0 * n * b,
            n * b + b * b - m * b,
            m * b + b - n,
        ]
    ) * a
    row_d = _arr([0.0, -1.0, 0.0, 1.0, 0.0])
    return (col_f, row_f), (col_d, row_d)


# ---------------------------------------------------------------------------
# Dense 5x5 filters
# ---------------------------------------------------------------------------

def kx(p: SobelParams = SobelParams()) -> np.ndarray:
    a, col, row = kx_factors(p)
    return a * np.outer(col, row)


def ky(p: SobelParams = SobelParams()) -> np.ndarray:
    a, col, row = ky_factors(p)
    return a * np.outer(col, row)


def kd(p: SobelParams = SobelParams()) -> np.ndarray:
    """45-degree filter (paper Eq. 5, third block)."""
    a, b, m, n = p.as_tuple()
    k = _arr(
        [
            [-m, -n, -1, -b, 0],
            [-n, -m * b, -n * b, 0, b],
            [-1, -n * b, 0, n * b, 1],
            [-b, 0, n * b, m * b, n],
            [0, b, 1, n, m],
        ]
    )
    return a * k


def kdt(p: SobelParams = SobelParams()) -> np.ndarray:
    """135-degree filter (paper Eq. 5, fourth block)."""
    a, b, m, n = p.as_tuple()
    k = _arr(
        [
            [0, -b, -1, -n, -m],
            [b, 0, -n * b, -m * b, -n],
            [1, n * b, 0, -n * b, -1],
            [n, m * b, n * b, 0, -b],
            [m, n, 1, b, 0],
        ]
    )
    return a * k


def kd_plus(p: SobelParams = SobelParams()) -> np.ndarray:
    """K_d+ = K_d + K_dt (Eq. 10)."""
    return kd(p) + kdt(p)


def kd_minus(p: SobelParams = SobelParams()) -> np.ndarray:
    """K_d- = K_d - K_dt (Eq. 10)."""
    return kd(p) - kdt(p)


def filter_bank_5x5(p: SobelParams = SobelParams()) -> np.ndarray:
    """(4, 5, 5) stack: [K_x, K_y, K_d, K_dt] — paper Eq. 3 when p is default."""
    return np.stack([kx(p), ky(p), kd(p), kdt(p)], axis=0)


# ---------------------------------------------------------------------------
# Classical 3x3 filters (baseline operator; paper Table 1 "3x3" rows)
# ---------------------------------------------------------------------------

SOBEL3_GX = _arr([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]])
SOBEL3_GY = _arr([[-1, -2, -1], [0, 0, 0], [1, 2, 1]])
# 45 / 135 degree 3x3 (Fig. 1(c)'s four-directional operator).
SOBEL3_GD = _arr([[-2, -1, 0], [-1, 0, 1], [0, 1, 2]])
SOBEL3_GDT = _arr([[0, -1, -2], [1, 0, -1], [2, 1, 0]])


def filter_bank_3x3(directions: int = 2) -> np.ndarray:
    """(D, 3, 3) stack of the classical 3x3 Sobel filters."""
    if directions == 2:
        return np.stack([SOBEL3_GX, SOBEL3_GY], axis=0)
    if directions == 4:
        return np.stack([SOBEL3_GX, SOBEL3_GY, SOBEL3_GD, SOBEL3_GDT], axis=0)
    raise ValueError(f"directions must be 2 or 4, got {directions}")


def as_jnp(bank: np.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.asarray(bank, dtype=dtype)


# ---------------------------------------------------------------------------
# Declarative operator registry
# ---------------------------------------------------------------------------

def _tupleize(a) -> tuple:
    """np array -> nested tuple of python floats (hashable, exact f32 values)."""
    a = np.asarray(a, np.float32)
    if a.ndim == 1:
        return tuple(float(v) for v in a)
    return tuple(_tupleize(row) for row in a)


@dataclasses.dataclass(frozen=True)
class OperatorSpec:
    """One edge operator, declaratively: everything the stack needs to run it.

    All array-valued fields are stored as nested tuples of exact f32 values,
    so a spec is hashable — it can be a jit static argument and (being
    registered as a static pytree) crosses transformation boundaries freely.

    Fields:
      name:       registry key (``"sobel5"``, ``"scharr3"``, ...).
      size:       odd kernel side length (3 / 5 / 7 / ...).
      directions: supported direction counts, e.g. ``(2, 4)``.
      variants:   supported algorithmic variants in ladder order, e.g.
                  ``("direct", "separable", "v1", "v2")``. Requesting an
                  unsupported ladder variant resolves to the best supported
                  one (see :meth:`resolve_variant`).
      taps:       ``(D_max, size, size)`` dense correlation taps in direction
                  order ``(K_x, K_y[, K_d, K_dt])``.
      sep:        per-direction ``(col, row)`` separable factors (or None
                  for directions that are only available dense). ``K = col
                  (x) row`` must hold exactly; enforced at registration.
      v2_factors: the paper's Eq. 18 split of K_d- as
                  ``(col_f, col_d, row_d)`` — ``row_f`` is K_x's row vector
                  by construction (RG-v2's key reuse), so it is not stored.
                  Present only when the ``v2`` variant is supported.
    """

    name: str
    size: int
    directions: Tuple[int, ...]
    variants: Tuple[str, ...]
    taps: tuple
    sep: tuple
    v2_factors: Optional[tuple] = None

    def __post_init__(self):
        if self.size % 2 != 1 or self.size < 3:
            raise ValueError(f"operator size must be odd >= 3, got {self.size}")
        if len(self.taps) < max(self.directions):
            raise ValueError(
                f"{self.name}: {len(self.taps)} tap matrices for "
                f"directions={self.directions}"
            )
        for k in self.taps:
            if len(k) != self.size or any(len(r) != self.size for r in k):
                raise ValueError(f"{self.name}: taps are not {self.size}x{self.size}")

    # -- geometry -----------------------------------------------------------
    @property
    def radius(self) -> int:
        return self.size // 2

    # -- numeric views (tuples -> arrays at trace time; exact round-trip) ---
    def bank(self, directions: Optional[int] = None) -> np.ndarray:
        """(D, size, size) dense f32 filter bank."""
        d = directions or max(self.directions)
        return np.asarray(self.taps[:d], np.float32)

    def sep_factors(self, direction: int) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(col, row) f32 factors of direction ``direction``, or None."""
        if direction >= len(self.sep) or self.sep[direction] is None:
            return None
        col, row = self.sep[direction]
        return np.asarray(col, np.float32), np.asarray(row, np.float32)

    def kd_plus_dense(self) -> np.ndarray:
        """K_d+ = K_d + K_dt (Eq. 10)."""
        return self.bank(4)[2] + self.bank(4)[3]

    def kd_minus_dense(self) -> np.ndarray:
        """K_d- = K_d - K_dt (Eq. 10)."""
        return self.bank(4)[2] - self.bank(4)[3]

    def v2_arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(col_f, col_d, row_d) f32 arrays of the Eq. 18 split."""
        assert self.v2_factors is not None
        col_f, col_d, row_d = self.v2_factors
        return (
            np.asarray(col_f, np.float32),
            np.asarray(col_d, np.float32),
            np.asarray(row_d, np.float32),
        )

    # -- request resolution -------------------------------------------------
    def resolve_variant(self, variant: Optional[str]) -> str:
        """Map a requested ladder variant onto this operator.

        ``None``/``"auto"`` -> the operator's best (last) variant. A known
        ladder variant the operator doesn't implement falls back to the best
        supported one (e.g. 3x3 has no diagonal transform: v2 -> separable),
        preserving the pre-registry coercion behavior. Unknown names raise.
        """
        ladder = ("direct", "separable", "v1", "v2")
        if variant is None or variant == "auto":
            return self.variants[-1]
        if variant in self.variants:
            return variant
        if variant in ladder:
            best = [v for v in self.variants if ladder.index(v) <= ladder.index(variant)]
            return best[-1] if best else self.variants[0]
        raise ValueError(
            f"unknown variant {variant!r}; expected one of {ladder}"
        )

    def resolve_directions(self, directions: Optional[int]) -> int:
        """``None``/``0`` -> the operator's max; otherwise validate."""
        if not directions:
            return max(self.directions)
        if directions not in self.directions:
            raise ValueError(
                f"operator {self.name!r} supports directions {self.directions}, "
                f"got {directions}"
            )
        return directions


# A spec carries only static data — register it as a leafless pytree so jit
# treats it by-value (hashable equality), like a string or an int.
jax.tree_util.register_static(OperatorSpec)


def _check_sep_reconstructs(spec: OperatorSpec) -> None:
    """Separable factors must reconstruct the dense taps *exactly* (f32)."""
    for d in range(len(spec.taps)):
        fac = spec.sep_factors(d)
        if fac is None:
            continue
        col, row = fac
        dense = np.outer(col, row).astype(np.float32)
        if not np.array_equal(dense, spec.bank(d + 1)[d]):
            raise ValueError(
                f"{spec.name}: separable factors of direction {d} do not "
                "reconstruct the dense taps exactly"
            )


_OPERATOR_BUILDERS: Dict[str, Callable[[Optional[SobelParams]], OperatorSpec]] = {}


def register_operator(
    name: str,
    builder: "Callable[[Optional[SobelParams]], OperatorSpec] | OperatorSpec",
    *,
    overwrite: bool = False,
) -> None:
    """Register an operator under ``name``.

    ``builder`` is either a constant :class:`OperatorSpec` or a callable
    ``params -> OperatorSpec`` (the Sobel 5x5 family is parameterized by
    :class:`SobelParams`; fixed-weight operators ignore ``params``). The
    separable-factor/dense-tap consistency invariant is enforced here.
    """
    if name in _OPERATOR_BUILDERS and not overwrite:
        raise ValueError(f"operator {name!r} already registered")
    if isinstance(builder, OperatorSpec):
        spec = builder

        def builder(_params, _spec=spec):  # noqa: F811 — constant spec closure
            return _spec

    _check_sep_reconstructs(builder(None))
    _OPERATOR_BUILDERS[name] = builder
    get_operator.cache_clear()


@functools.lru_cache(maxsize=128)
def get_operator(name: str, params: Optional[SobelParams] = None) -> OperatorSpec:
    """Look up a registered operator (optionally with custom weights)."""
    if name not in _OPERATOR_BUILDERS:
        raise KeyError(
            f"unknown operator {name!r}; registered: {sorted(_OPERATOR_BUILDERS)}"
        )
    return _OPERATOR_BUILDERS[name](params)


def list_operators() -> Tuple[str, ...]:
    return tuple(sorted(_OPERATOR_BUILDERS))


def operator_for_size(size: int) -> str:
    """Legacy ``size=3|5`` kwargs -> registry name (back-compat shims)."""
    names = {3: "sobel3", 5: "sobel5", 7: "sobel7"}
    if size not in names:
        raise ValueError(f"size must be one of {sorted(names)}, got {size}")
    return names[size]


def make_separable_spec(
    name: str,
    col: "np.ndarray | tuple",
    row: "np.ndarray | tuple",
) -> OperatorSpec:
    """Build a 2-direction spec from one separable derivative filter.

    ``K_x = col (x) row`` and ``K_y = K_x^T`` — the shape of every classical
    derivative operator (Sobel/Scharr/Prewitt and their extensions). This is
    also the documented hook for registering custom operators (DESIGN.md §5).
    """
    col = np.asarray(col, np.float32)
    row = np.asarray(row, np.float32)
    if col.ndim != 1 or col.shape != row.shape:
        raise ValueError("col/row must be equal-length 1-D vectors")
    gx = np.outer(col, row).astype(np.float32)
    gy = gx.T.copy()
    return OperatorSpec(
        name=name,
        size=int(col.shape[0]),
        directions=(2,),
        variants=("direct", "separable"),
        taps=_tupleize(np.stack([gx, gy])),
        sep=(( _tupleize(col), _tupleize(row)), (_tupleize(row), _tupleize(col))),
    )


# -- built-in specs ---------------------------------------------------------

def _sobel5_builder(params: Optional[SobelParams]) -> OperatorSpec:
    p = params or SobelParams()
    a, col_x, row_x = kx_factors(p)
    _, col_y, row_y = ky_factors(p)
    (col_f, _row_f), (col_d, row_d) = kd_minus_factors(p)
    return OperatorSpec(
        name="sobel5",
        size=5,
        directions=(2, 4),
        variants=("direct", "separable", "v1", "v2"),
        taps=_tupleize(filter_bank_5x5(p)),
        # a folded into the columns exactly as the pre-registry code computed
        # it (``a * col`` in numpy f32) — keeps outputs bit-identical.
        sep=((_tupleize(a * col_x), _tupleize(row_x)),
             (_tupleize(a * col_y), _tupleize(row_y))),
        v2_factors=(_tupleize(col_f), _tupleize(col_d), _tupleize(row_d)),
    )


def _sobel3_builder(params: Optional[SobelParams]) -> OperatorSpec:
    # 3x3 has no SobelParams generalization; params are accepted-and-ignored
    # to honor the legacy ``sobel(size=3, params=...)`` call shape.
    return OperatorSpec(
        name="sobel3",
        size=3,
        directions=(2, 4),
        variants=("direct", "separable"),
        taps=_tupleize(filter_bank_3x3(4)),
        sep=((_tupleize([1.0, 2.0, 1.0]), _tupleize([-1.0, 0.0, 1.0])),
             (_tupleize([-1.0, 0.0, 1.0]), _tupleize([1.0, 2.0, 1.0]))),
    )


# Extended 7x7 Sobel (Bogdan et al. 2019, "Custom Extended Sobel Filters"):
# binomial smoothing of order 6 x the order-7 Sobel derivative vector —
# identical to OpenCV's getDerivKernels(1, 0, ksize=7).
_SOBEL7_SMOOTH = (1.0, 6.0, 15.0, 20.0, 15.0, 6.0, 1.0)
_SOBEL7_DERIV = (-1.0, -4.0, -5.0, 0.0, 5.0, 4.0, 1.0)

register_operator("sobel5", _sobel5_builder)
register_operator("sobel3", _sobel3_builder)
register_operator(
    "scharr3", make_separable_spec("scharr3", (3.0, 10.0, 3.0), (-1.0, 0.0, 1.0))
)
register_operator(
    "prewitt3", make_separable_spec("prewitt3", (1.0, 1.0, 1.0), (-1.0, 0.0, 1.0))
)
register_operator(
    "sobel7", make_separable_spec("sobel7", _SOBEL7_SMOOTH, _SOBEL7_DERIV)
)


# ---------------------------------------------------------------------------
# Stages and StencilPlans — the declarative multi-stage stencil layer
# ---------------------------------------------------------------------------
#
# A plan is an ordered, frozen sequence of stages; its *reach* (the sum of
# stage radii, +1 for a trailing NMS stage) is the single halo number that
# `kernels.tiling.window_radius`, `sharding.halo.exchange_radius`, and the
# fused kernel window all derive from — so a Gaussian5 -> sobel5 -> NMS Canny
# plan ships as ONE Pallas launch with a (r_blur + r_grad + 1) halo.
#
# Validation is gate-named: every rejection message carries the literal gate
# name (`plan gate 'unknown-stage'`, `'frozen-stage'`, `'window-radius'`,
# `'nms-last'`, ...) so tests and callers can pin the failing invariant.

_STAGE_KINDS = ("linear", "pointwise", "window_reduce", "nms")
_WINDOW_OPS = ("max", "min")


@dataclasses.dataclass(frozen=True)
class Stage:
    """One step of a :class:`StencilPlan`.

    Kinds:
      linear:        correlation with ``operator``'s taps. Single-direction
                     specs (``directions=(1,)``) are smoothing pre-stages; a
                     multi-direction spec is the plan's gradient stage.
      pointwise:     shape-preserving map; ``op`` names a registered
                     pointwise fn (:func:`register_pointwise`). radius 0.
      window_reduce: separable max/min over a ``(2r+1)``-square window
                     (morphological dilate/erode); ``op`` in ``max | min``.
      nms:           the fused non-maximum-suppression stage (radius 1, last
                     stage only) — thin-map semantics of ``repro.core.nms``.

    ``radius`` is the stage's halo contribution; for linear stages it must
    equal the operator's radius (use :func:`linear_stage`).
    """

    name: str
    kind: str
    operator: Optional[OperatorSpec] = None
    op: Optional[str] = None
    radius: int = 0

    def __post_init__(self):
        if self.kind not in _STAGE_KINDS:
            raise ValueError(
                f"plan gate 'stage-kind': stage {self.name!r} has unknown "
                f"kind {self.kind!r}; expected one of {_STAGE_KINDS}"
            )
        if self.kind == "linear":
            if self.operator is None:
                raise ValueError(
                    f"plan gate 'stage-kind': linear stage {self.name!r} "
                    "needs an OperatorSpec"
                )
            if self.radius != self.operator.radius:
                raise ValueError(
                    f"plan gate 'stage-radius': linear stage {self.name!r} "
                    f"declares radius {self.radius} but its operator has "
                    f"radius {self.operator.radius}"
                )
        elif self.kind == "pointwise":
            if self.radius != 0:
                raise ValueError(
                    f"plan gate 'stage-radius': pointwise stage "
                    f"{self.name!r} must have radius 0, got {self.radius}"
                )
            if self.op not in _POINTWISE_FNS:
                raise ValueError(
                    f"plan gate 'unknown-pointwise': stage {self.name!r} "
                    f"names pointwise fn {self.op!r}; registered: "
                    f"{sorted(_POINTWISE_FNS)}"
                )
        elif self.kind == "window_reduce":
            if self.op not in _WINDOW_OPS:
                raise ValueError(
                    f"plan gate 'window-op': window-reduce stage "
                    f"{self.name!r} needs op in {_WINDOW_OPS}, got {self.op!r}"
                )
            if self.radius < 1:
                raise ValueError(
                    f"plan gate 'window-radius': window-reduce stage "
                    f"{self.name!r} must have radius >= 1, got {self.radius} "
                    "(a zero-radius window reduces nothing)"
                )
        elif self.kind == "nms":
            if self.radius != 1:
                raise ValueError(
                    f"plan gate 'stage-radius': the NMS stage reaches "
                    f"exactly 1 pixel, got radius {self.radius}"
                )

    @property
    def single_plane(self) -> bool:
        """True when the stage maps one plane to one plane (a pre-stage)."""
        if self.kind == "linear":
            return max(self.operator.directions) == 1
        return self.kind in ("pointwise", "window_reduce")


def linear_stage(name: str, operator: OperatorSpec) -> Stage:
    return Stage(name=name, kind="linear", operator=operator,
                 radius=operator.radius)


def pointwise_stage(name: str, fn: str) -> Stage:
    return Stage(name=name, kind="pointwise", op=fn, radius=0)


def window_stage(name: str, op: str, radius: int) -> Stage:
    return Stage(name=name, kind="window_reduce", op=op, radius=radius)


def _stage_is_frozen(stage) -> bool:
    params = getattr(type(stage), "__dataclass_params__", None)
    return params is not None and bool(params.frozen)


@dataclasses.dataclass(frozen=True)
class StencilPlan:
    """An ordered, frozen sequence of stages fused into one kernel launch.

    Structure (validated here): zero or more *single-plane* pre-stages
    (smoothing, morphology, pointwise), then at most one multi-direction
    linear *gradient* stage, then optionally the NMS stage — which must be
    last (it consumes the gradient's direction components).

    ``linear_reach`` is the sum of all non-NMS stage radii; ``reach`` adds
    NMS's +1. Both are static, so a plan is hashable and jit-static exactly
    like an :class:`OperatorSpec`.
    """

    name: str
    stages: Tuple[Stage, ...]

    def __post_init__(self):
        if not self.stages:
            raise ValueError(
                f"plan gate 'empty-plan': plan {self.name!r} has no stages"
            )
        for i, stage in enumerate(self.stages):
            if not _stage_is_frozen(stage):
                raise ValueError(
                    f"plan gate 'frozen-stage': stage "
                    f"{getattr(stage, 'name', stage)!r} of plan "
                    f"{self.name!r} is not a frozen dataclass — plans must "
                    "be hashable to cross jit boundaries"
                )
            if not isinstance(stage, Stage):
                raise ValueError(
                    f"plan gate 'stage-kind': plan {self.name!r} got a "
                    f"non-Stage entry {stage!r}"
                )
            if stage.kind == "nms" and i != len(self.stages) - 1:
                raise ValueError(
                    f"plan gate 'nms-last': plan {self.name!r} places the "
                    f"NMS stage at position {i}; NMS consumes the gradient "
                    "components and must be the last stage"
                )
        body = self.body
        for stage in body[:-1]:
            if not stage.single_plane:
                raise ValueError(
                    f"plan gate 'gradient-last': plan {self.name!r} places "
                    f"multi-direction stage {stage.name!r} before the end; "
                    "only the final non-NMS stage may produce direction "
                    "components"
                )
        if self.nms:
            if not body or body[-1].single_plane:
                raise ValueError(
                    f"plan gate 'nms-gradient': plan {self.name!r} has an "
                    "NMS stage but no multi-direction gradient stage to "
                    "feed it"
                )

    # -- structure ----------------------------------------------------------
    @property
    def nms(self) -> bool:
        return self.stages[-1].kind == "nms"

    @property
    def body(self) -> Tuple[Stage, ...]:
        """All stages except a trailing NMS stage."""
        return self.stages[:-1] if self.nms else self.stages

    @property
    def gradient(self) -> Optional[OperatorSpec]:
        """The multi-direction operator of the final body stage, if any."""
        body = self.body
        if body and not body[-1].single_plane:
            return body[-1].operator
        return None

    @property
    def pre_stages(self) -> Tuple[Stage, ...]:
        """Single-plane stages ahead of the gradient (or the whole body)."""
        body = self.body
        return body[:-1] if self.gradient is not None else body

    # -- geometry (the composed-halo single source of truth) ----------------
    @property
    def linear_reach(self) -> int:
        """Sum of non-NMS stage radii — the composed correlation radius."""
        return sum(s.radius for s in self.body)

    @property
    def reach(self) -> int:
        """Total halo reach including NMS's +1 neighbourhood."""
        return self.linear_reach + (1 if self.nms else 0)

    @property
    def single_operator(self) -> bool:
        """True when the plan is exactly one gradient stage (+ maybe NMS) —
        the engine then takes the historical single-operator kernel path."""
        return not self.pre_stages and self.gradient is not None


jax.tree_util.register_static(Stage)
jax.tree_util.register_static(StencilPlan)


def plan_identity(plan: StencilPlan) -> str:
    """Stable cache identity: plan name + hash of stage names and radii.

    This is the TuneKey plan segment — multi-stage tunings cannot
    collide with single-operator entries or with a differently-shaped plan
    that reuses a name.
    """
    import hashlib

    sig = "|".join(f"{s.name}:{s.kind}:{s.radius}" for s in plan.stages)
    return f"{plan.name}.{hashlib.sha1(sig.encode()).hexdigest()[:8]}"


# -- pointwise registry -----------------------------------------------------

# name -> (fn, int_bound). ``fn`` must be exact in both lanes (fenced f32 /
# plain integer); ``int_bound`` maps an input magnitude bound to the output
# bound for the integer-lane proof, or None when the fn is int-ineligible.
_POINTWISE_FNS: Dict[str, tuple] = {}


def register_pointwise(name, fn, *, int_bound=None, overwrite: bool = False):
    if name in _POINTWISE_FNS and not overwrite:
        raise ValueError(f"pointwise fn {name!r} already registered")
    _POINTWISE_FNS[name] = (fn, int_bound)


def get_pointwise(name):
    if name not in _POINTWISE_FNS:
        raise ValueError(
            f"plan gate 'unknown-pointwise': unknown pointwise fn {name!r}; "
            f"registered: {sorted(_POINTWISE_FNS)}"
        )
    return _POINTWISE_FNS[name]


def _square_fenced(x):
    # max(x*x, 0) is an exact identity for squares that blocks FMA
    # contraction of the multiply (same fence as core.sobel.magnitude).
    return jnp.maximum(x * x, jnp.zeros((), x.dtype))


register_pointwise("abs", jnp.abs, int_bound=lambda m: m)
register_pointwise("square", _square_fenced, int_bound=lambda m: m * m)


# -- stage registry ---------------------------------------------------------

_STAGE_REGISTRY: Dict[str, Stage] = {}


def register_stage(name: str, stage: Stage, *, overwrite: bool = False) -> None:
    if name in _STAGE_REGISTRY and not overwrite:
        raise ValueError(f"stage {name!r} already registered")
    if stage.kind == "linear":
        _check_sep_reconstructs(stage.operator)
    _STAGE_REGISTRY[name] = stage


def get_stage(name: str) -> Stage:
    if name not in _STAGE_REGISTRY:
        raise ValueError(
            f"plan gate 'unknown-stage': unknown stage {name!r}; registered "
            f"stages: {sorted(_STAGE_REGISTRY)}; registered operators (usable "
            f"as gradient stages): {list_operators()}"
        )
    return _STAGE_REGISTRY[name]


def list_stages() -> Tuple[str, ...]:
    return tuple(sorted(_STAGE_REGISTRY))


def _gaussian_stage(name: str, g) -> Stage:
    """Separable binomial smoothing stage. The normalized taps are dyadic
    (denominator a power of two), so every tap and every outer-product
    entry is exact in f32 — the separable factors reconstruct the dense
    taps bit-exactly, and the fenced f32 lane stays deterministic."""
    g = np.asarray(g, np.float32)
    g = (g / np.float32(g.sum())).astype(np.float32)
    k = np.outer(g, g).astype(np.float32)
    spec = OperatorSpec(
        name=name,
        size=int(g.shape[0]),
        directions=(1,),
        variants=("direct", "separable"),
        taps=_tupleize(k[None]),
        sep=((_tupleize(g), _tupleize(g)),),
    )
    return linear_stage(name, spec)


register_stage("gaussian3", _gaussian_stage("gaussian3", (1.0, 2.0, 1.0)))
register_stage("gaussian5", _gaussian_stage("gaussian5", (1.0, 4.0, 6.0, 4.0, 1.0)))
register_stage("dilate3", window_stage("dilate3", "max", 1))
register_stage("erode3", window_stage("erode3", "min", 1))
register_stage("nms", Stage(name="nms", kind="nms", radius=1))


# -- plan registry ----------------------------------------------------------

def _resolve_stage_ref(ref) -> Stage:
    """A plan entry: a Stage, a registered stage name, a registered operator
    name (gradient stage), or an OperatorSpec."""
    if isinstance(ref, Stage):
        return ref
    if isinstance(ref, OperatorSpec):
        return linear_stage(ref.name, ref)
    if isinstance(ref, str):
        if ref in _STAGE_REGISTRY:
            return _STAGE_REGISTRY[ref]
        if ref in _OPERATOR_BUILDERS:
            return linear_stage(ref, get_operator(ref))
        raise ValueError(
            f"plan gate 'unknown-stage': unknown stage {ref!r}; registered "
            f"stages: {sorted(_STAGE_REGISTRY)}; registered operators (usable "
            f"as gradient stages): {list_operators()}"
        )
    # Anything else (e.g. a custom stage-like object) is validated by
    # StencilPlan.__post_init__'s frozen-stage / stage-kind gates.
    return ref


def make_plan(name: str, stages) -> StencilPlan:
    return StencilPlan(name=name,
                       stages=tuple(_resolve_stage_ref(s) for s in stages))


_PLAN_REGISTRY: Dict[str, StencilPlan] = {}


def register_plan(name: str, stages, *, overwrite: bool = False) -> StencilPlan:
    if name in _PLAN_REGISTRY and not overwrite:
        raise ValueError(f"plan {name!r} already registered")
    plan = stages if isinstance(stages, StencilPlan) else make_plan(name, stages)
    _PLAN_REGISTRY[name] = plan
    return plan


def get_plan(name: str) -> StencilPlan:
    if name not in _PLAN_REGISTRY:
        raise ValueError(
            f"plan gate 'unknown-plan': unknown plan {name!r}; registered: "
            f"{sorted(_PLAN_REGISTRY)}"
        )
    return _PLAN_REGISTRY[name]


def list_plans() -> Tuple[str, ...]:
    return tuple(sorted(_PLAN_REGISTRY))


def resolve_plan(plan) -> Optional[StencilPlan]:
    """``None`` | plan name | StencilPlan -> validated StencilPlan or None."""
    if plan is None:
        return None
    if isinstance(plan, StencilPlan):
        return plan
    if isinstance(plan, str):
        return get_plan(plan)
    raise TypeError(
        f"plan must be a StencilPlan or a registered plan name, got "
        f"{type(plan).__name__}"
    )


# The built-in plans: the full Canny front half (blur -> 4-direction
# gradient -> NMS; hysteresis stays a post-gather linking pass, DESIGN §7)
# and its no-NMS sibling. canny5's reach is 2 + 2 + 1 = 5.
register_plan("canny5", ("gaussian5", "sobel5", "nms"))
register_plan("blur_sobel5", ("gaussian5", "sobel5"))
