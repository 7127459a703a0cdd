"""Trace-level contract rules for the fused edge engine.

Each ``check_*`` function takes a traced artifact — a ClosedJaxpr from
``jax.make_jaxpr``, a StableHLO module string from ``jax.export`` with
``platforms=["tpu"]``, or an :class:`~repro.core.filters.OperatorSpec` —
and returns a list of :class:`~repro.analysis.violations.Violation`.
Nothing here executes a kernel: jaxprs are walked with
:func:`repro.roofline.hlo.iter_jaxpr_eqns`, and the only evaluation is
of BlockSpec *index maps* (a handful of scalar clamps) to recover the
halo geometry the kernel actually compiled with.

Rule IDs are stable and documented in DESIGN.md §10; the committed
baseline (``analysis_baseline.json``) keys off ``RULE|location``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jax_core
import jax.numpy as jnp

from repro.analysis.violations import Violation
from repro.core.ladder import tap_accumulation_bounds
from repro.roofline.hlo import (
    DATA_PREP_PRIMITIVES,
    iter_jaxpr_eqns,
    stablehlo_op_counts,
    subjaxprs,
)

__all__ = [
    "RULES",
    "Rule",
    "AnalysisError",
    "check_fusion_purity",
    "check_kernel_cardinality",
    "check_mosaic_program",
    "check_contraction_fences",
    "check_dtype_ladder",
    "check_kernel_accum_dtype",
    "check_vmem_budget",
    "check_halo_window",
    "check_static_registration",
    "find_pallas_eqns",
    "tap_accumulation_bounds",
]


class AnalysisError(RuntimeError):
    """The analyzer itself was misused (bad geometry, unexpected trace
    shape) — distinct from a rule violation in the analyzed program."""


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    guards: str
    since: str


RULES: Dict[str, Rule] = {
    r.id: r
    for r in [
        Rule(
            "FUSE001",
            "fusion-purity",
            "no pad/slice/gather/concat staging in a fused path's HBM-level "
            "jaxpr (kernel bodies are opaque; component unstacking and the "
            "post-gather hysteresis fixpoint are scoped allowances)",
            "PR 2 (spy tests) / PR 8 (rule)",
        ),
        Rule(
            "FUSE002",
            "kernel-cardinality",
            "exactly one pallas_call per fused launch — gray→gradient→NMS "
            "stay one kernel",
            "PR 2 / PR 8",
        ),
        Rule(
            "FUSE003",
            "mosaic-purity",
            "the TPU-lowered StableHLO has no pad/slice/dynamic_slice and "
            "exactly one tpu_custom_call",
            "PR 2 / PR 8",
        ),
        Rule(
            "FMA001",
            "contraction-safety",
            "no float mul feeding add/sub directly — unfenced tap chains "
            "invite FMA contraction and break cross-backend bit-exactness "
            "(fenced chains go mul→max→add)",
            "PR 3 (fence idiom) / PR 8 (rule)",
        ),
        Rule(
            "DTYPE001",
            "dtype-ladder",
            "u8 input × integer taps accumulates exactly in f32 (≤ 2^24), "
            "u16 input keeps the integer lane exactly where its bound at "
            "65535 does, and the traced kernel's actual integer "
            "accumulation dtype (recovered from its u8/u16→int entry cast) "
            "equals the narrowest dtype the ladder proof licenses for that "
            "frame dtype (core.ladder.accum_dtype)",
            "PR 8 (spec proof) / PR 9 (kernel check)",
        ),
        Rule(
            "VMEM001",
            "vmem-budget",
            "block + halo + intermediates working set fits the per-core "
            "VMEM budget (tuning.VMEM_BUDGET), incl. default_block_shape",
            "PR 2 / PR 8",
        ),
        Rule(
            "HALO001",
            "halo-consistency",
            "every input window the compiled Element index map reads covers "
            "OperatorSpec.radius (+1 under NMS), which equals the sharded "
            "exchange width (tiling.window_radius is the single source)",
            "PR 4 / PR 8",
        ),
        Rule(
            "DET001",
            "no-wall-clock-or-randomness",
            "kernel-math modules import no time/random/uuid/secrets and "
            "call no RNG — retrace must be reproducible",
            "PR 8",
        ),
        Rule(
            "DET002",
            "no-python-branch-on-tracer",
            "no Python if/while/assert on a jnp expression in kernel-math "
            "modules — branch decisions must be static or in-graph",
            "PR 8",
        ),
        Rule(
            "DET003",
            "static-pytrees-hashable",
            "register_static targets are frozen dataclasses (hashable, "
            "eq-by-value) so configs/specs are valid jit static args",
            "PR 3 / PR 8",
        ),
    ]
}

# Staging primitives that may never appear at the HBM level of a fused
# path, and the slice-flavored subset eligible for the component-unstack
# allowance.
_SLICE_PRIMS = ("slice", "dynamic_slice")


def find_pallas_eqns(jaxpr) -> List[object]:
    """All pallas_call equations reachable from ``jaxpr`` (kernel bodies
    are leaves, so nested kernels would each be reported once)."""
    return [
        eqn
        for eqn in iter_jaxpr_eqns(jaxpr, opaque=("pallas_call",))
        if eqn.primitive.name == "pallas_call"
    ]


def _is_component_unstack(eqn) -> bool:
    """A ``slice`` that peels one direction plane off the stacked
    component axis: (N, D, H, W) -> (N, 1, H, W). The only HBM-level
    slicing the fused engine performs, and only in the with_components /
    with_orientation output modes (the stack itself comes out of the one
    kernel launch)."""
    if eqn.primitive.name != "slice":
        return False
    src = eqn.invars[0].aval.shape
    dst = eqn.outvars[0].aval.shape
    return (
        len(src) == len(dst)
        and len(src) >= 3
        and src[1] > 1
        and dst[1] == 1
        and src[0] == dst[0]
        and tuple(src[2:]) == tuple(dst[2:])
    )


def check_fusion_purity(
    jaxpr,
    *,
    location: str,
    allow_unstack: bool = False,
    opaque: Sequence[str] = ("pallas_call",),
) -> List[Violation]:
    """FUSE001: no data-prep staging primitives at the HBM level.

    ``opaque`` lists primitives whose bodies are off-limits to the walk;
    fused paths use ``("pallas_call",)``, and hysteresis mode adds
    ``"while"`` because the post-gather linking fixpoint dilates with
    ``jnp.pad`` *by design* (it runs after the kernel's gather stage).
    """
    out: List[Violation] = []
    hits: Dict[str, int] = {}
    allowed = 0
    for eqn in iter_jaxpr_eqns(jaxpr, opaque=tuple(opaque)):
        name = eqn.primitive.name
        if name not in DATA_PREP_PRIMITIVES:
            continue
        if allow_unstack and _is_component_unstack(eqn):
            allowed += 1
            continue
        hits[name] = hits.get(name, 0) + 1
    for name, n in sorted(hits.items()):
        out.append(
            Violation(
                "FUSE001",
                location,
                f"{n} HBM-level `{name}` op(s) in a fused path",
                detail=(("primitive", name), ("count", str(n))),
            )
        )
    return out


def check_kernel_cardinality(
    jaxpr, *, location: str, expected: int = 1
) -> List[Violation]:
    """FUSE002: a fused path launches exactly ``expected`` kernels."""
    n = len(find_pallas_eqns(jaxpr))
    if n == expected:
        return []
    return [
        Violation(
            "FUSE002",
            location,
            f"{n} pallas_call launch(es), expected {expected}",
            detail=(("pallas_calls", str(n)), ("expected", str(expected))),
        )
    ]


def check_mosaic_program(mlir_text: str, *, location: str) -> List[Violation]:
    """FUSE003: the TPU-exported StableHLO stages nothing around the one
    custom call. Interpret-mode lowerings are NOT valid inputs here (the
    interpreter pads carries to block multiples internally)."""
    out: List[Violation] = []
    counts = stablehlo_op_counts(mlir_text)
    for name in ("pad", "slice", "dynamic_slice", "gather", "scatter"):
        n = counts.get(name, 0)
        if n:
            out.append(
                Violation(
                    "FUSE003",
                    location,
                    f"{n} stablehlo.{name} op(s) in the TPU-lowered module",
                    detail=(("op", name), ("count", str(n))),
                )
            )
    calls = mlir_text.count("tpu_custom_call")
    if calls != 1:
        out.append(
            Violation(
                "FUSE003",
                location,
                f"{calls} tpu_custom_call site(s) in the TPU-lowered module, expected 1",
                detail=(("tpu_custom_calls", str(calls)),),
            )
        )
    return out


def _is_float(var) -> bool:
    dtype = getattr(getattr(var, "aval", None), "dtype", None)
    return dtype is not None and jnp.issubdtype(dtype, jnp.floating)


def check_contraction_fences(jaxpr, *, location: str) -> List[Violation]:
    """FMA001: flag float ``mul`` results consumed directly by ``add`` /
    ``sub``. The engine's fence idiom (``jnp.maximum(w * x, _F32_LOWEST)``,
    see ``repro.core.sobel._tap``) puts a ``max`` between every tap
    product and its accumulation, which is exactly what keeps XLA from
    contracting the chain into FMAs and diverging across backends. The
    walk descends into kernel bodies: fences matter most inside the
    kernel."""
    out: List[Violation] = []

    def scope(jx):
        producers = {}
        for eqn in jx.eqns:
            for ov in eqn.outvars:
                producers[ov] = eqn
        for eqn in jx.eqns:
            if eqn.primitive.name in ("add", "sub", "add_any") and _is_float(
                eqn.outvars[0]
            ):
                for iv in eqn.invars:
                    p = producers.get(iv) if isinstance(iv, jax_core.Var) else None
                    if p is not None and p.primitive.name == "mul" and _is_float(iv):
                        out.append(
                            Violation(
                                "FMA001",
                                location,
                                "unfenced float mul feeding "
                                f"{eqn.primitive.name} (shape "
                                f"{tuple(iv.aval.shape)}) — insert a "
                                "maximum() fence between product and sum",
                                detail=(
                                    ("consumer", eqn.primitive.name),
                                    ("shape", str(tuple(iv.aval.shape))),
                                ),
                            )
                        )
        for eqn in jx.eqns:
            for sub in subjaxprs(eqn):
                scope(sub)

    scope(getattr(jaxpr, "jaxpr", jaxpr))
    return out


# tap_accumulation_bounds lives in repro.core.ladder (and is re-exported
# above): the kernels, the dispatcher's precision gate and this analyzer
# must all cite the *same* proof.


def check_dtype_ladder(spec, *, location: str, input_dtype="uint8"
                       ) -> List[Violation]:
    """DTYPE001 (spec half), for frames of ``input_dtype``.

    u8: integer-tap operators must accumulate u8 input exactly in f32 (all
    intermediates ≤ 2^24) — the contract both arithmetic lanes rely on: it
    is what makes the i16/i32 integer lane bit-identical to the f32 lane by
    construction. Deeper frames (u16, bound 65535): an operator whose bound
    leaves f32's exact range (sobel7) keeps no integer lane there, so the
    lane's gate (``core.ladder.int_lane_eligible``) must admit an operator
    exactly when its bound at the dtype's largest value is f32-exact and
    fits i32."""
    from repro.core import ladder

    m = ladder.frame_max(input_dtype)
    b = tap_accumulation_bounds(spec, input_max=m)
    if not b["integer_taps"]:
        return []  # fractional taps opt out of the integer ladder
    detail = (
        ("input_max", str(m)),
        ("worst", f"{b['worst']:.0f}"),
        ("fits_i16", str(b["fits_i16"])),
        ("fits_i32", str(b["fits_i32"])),
    )
    if jnp.dtype(input_dtype) == jnp.uint8:
        if b["f32_exact"]:
            return []
        return [
            Violation(
                "DTYPE001",
                location,
                f"integer-tap accumulation bound {b['worst']:.0f} exceeds the "
                f"f32-exact integer range (2^24); i16={b['fits_i16']}, "
                f"i32={b['fits_i32']}",
                detail=detail[1:],
            )
        ]
    exact = b["f32_exact"] and b["fits_i32"]
    admitted, _reason = ladder.int_lane_eligible(
        spec, rgb=False, input_dtype=input_dtype
    )
    if admitted == exact:
        return []
    return [
        Violation(
            "DTYPE001",
            location,
            f"the integer lane {'admits' if admitted else 'refuses'} "
            f"{jnp.dtype(input_dtype).name} frames of operator {spec.name!r}, "
            f"whose bound {b['worst']:.0f} at {m} "
            f"{'fits' if exact else 'leaves'} the f32-exact range and i32",
            detail=detail,
        )
    ]


def check_kernel_accum_dtype(jaxpr, *, location: str, spec) -> List[Violation]:
    """DTYPE001 (kernel half): the integer lane's *actual* accumulation
    dtype must equal the narrowest dtype the ladder proof licenses for the
    frame dtype it starts from.

    The lane entry is the only place a traced program converts a u8 or u16
    array (rank ≥ 2 — scalar index math never starts from them) to a
    signed integer that it computes in: ``x.astype(accum_dtype)`` in the
    kernels, or the XLA-path equivalent in ``sobel_components``/
    ``thin_map``. A cast whose every use converts on to a float is the f32
    lane widening through i32 (``tiling.as_f32``, as Mosaic requires), not
    an entry. The walk descends into kernel bodies. No entry ⇒ the trace
    is on the f32 lane and the check passes vacuously. An entry *narrower*
    than :func:`repro.core.ladder.accum_dtype` of its frame dtype — i16
    where the bound needs i32, as every u16 frame of sobel5 does — is the
    silent-wraparound bug this rule exists to catch; wider (i16-licensed
    math run in i32, as the TPU lane does around Mosaic's 16-bit gaps)
    stays exact and passes, while anything beyond i32 has no proof at all
    and fails.
    """
    from repro.core import ladder

    _WIDTH = {"int16": 16, "int32": 32}
    _SHORT = {"uint8": "u8", "uint16": "u16"}
    eqns = list(iter_jaxpr_eqns(jaxpr, opaque=()))
    uses: Dict[object, list] = {}
    for eqn in eqns:
        for v in eqn.invars:
            if isinstance(v, jax_core.Var):
                uses.setdefault(v, []).append(eqn)

    def widens_to_float(var) -> bool:
        return bool(uses.get(var)) and all(
            u.primitive.name == "convert_element_type"
            and jnp.issubdtype(u.outvars[0].aval.dtype, jnp.floating)
            for u in uses[var]
        )

    seen: Dict[str, List[str]] = {}   # frame dtype -> accumulation dtypes
    for eqn in eqns:
        if eqn.primitive.name != "convert_element_type":
            continue
        src = getattr(eqn.invars[0], "aval", None)
        dst = eqn.outvars[0].aval
        if src is None or len(getattr(dst, "shape", ())) < 2:
            continue
        if src.dtype.name not in _SHORT:
            continue
        if not jnp.issubdtype(dst.dtype, jnp.signedinteger):
            continue
        if widens_to_float(eqn.outvars[0]):
            continue
        found = seen.setdefault(src.dtype.name, [])
        if str(dst.dtype) not in found:
            found.append(str(dst.dtype))
    out: List[Violation] = []
    for frame, found in seen.items():
        expected = ladder.accum_dtype(spec, input_dtype=frame)
        if expected is None:
            out.append(
                Violation(
                    "DTYPE001",
                    location,
                    f"integer accumulation ({', '.join(found)}) of "
                    f"{_SHORT[frame]} frames in a trace of operator "
                    f"{spec.name!r}, which has no proven integer budget "
                    "for them (fractional taps or bound beyond 2^24)",
                    detail=(("frame", frame), ("found", ",".join(found)),
                            ("expected", "none")),
                )
            )
            continue
        out += [
            Violation(
                "DTYPE001",
                location,
                f"kernel accumulates {_SHORT[frame]} taps in {d}, but the "
                f"ladder proof licenses {expected} for operator {spec.name!r}"
                + ("" if d in _WIDTH else " (no proof covers this dtype)"),
                detail=(("frame", frame), ("found", d),
                        ("expected", expected)),
            )
            for d in found
            if d not in _WIDTH or _WIDTH[d] < _WIDTH[expected]
        ]
    return out


def check_vmem_budget(
    *,
    location: str,
    block_h: int,
    block_w: int,
    radius: int,
    nms: bool = False,
    channels: Optional[int] = None,
    budget: Optional[int] = None,
    plan=None,
) -> List[Violation]:
    """VMEM001: the per-grid-step working set (window + halo'd
    intermediates + output tile, f32) fits the VMEM budget.

    With ``plan`` (a :class:`~repro.core.filters.StencilPlan`) the window
    radius is the *composed* reach of the whole stage chain — the fused
    multi-stage kernel pads once by ``plan.linear_reach`` (+1 for a
    trailing NMS stage), not per stage."""
    from repro.kernels import tuning
    from repro.kernels.tiling import tile_vmem_bytes, window_radius

    cap = tuning.VMEM_BUDGET if budget is None else budget
    if plan is not None:
        r_in = window_radius(plan.linear_reach, nms or plan.nms)
    else:
        r_in = window_radius(radius, nms)
    need = tile_vmem_bytes(block_h, block_w, r_in, channels=channels)
    if need <= cap:
        return []
    return [
        Violation(
            "VMEM001",
            location,
            f"block ({block_h}, {block_w}) with r={r_in} needs "
            f"{need / 2**20:.1f} MiB VMEM > {cap / 2**20:.1f} MiB budget",
            detail=(("bytes", str(need)), ("budget", str(cap))),
        )
    ]


def _eval_index_map(bm, grid_indices: Tuple[int, ...]) -> List[int]:
    imj = bm.index_map_jaxpr
    args = [jnp.int32(g) for g in grid_indices]
    try:
        out = jax.core.eval_jaxpr(imj.jaxpr, imj.consts, *args)
    except Exception as e:  # arity/shape mismatch — analyzer misuse
        raise AnalysisError(f"cannot evaluate BlockSpec index map: {e}") from e
    return [int(o) for o in out]


def _element_window(bm) -> Optional[Tuple[int, int]]:
    """(tile_h, tile_w) of a halo'd input window — a BlockSpec whose block
    dims are all ``pl.Element`` (element-offset index map) over an
    ``(N, [C,] H, W)`` array — or ``None`` for any other block mapping."""
    dims = tuple(bm.block_shape)
    if len(dims) < 3 or not all(type(d).__name__ == "Element" for d in dims):
        return None
    return dims[-2].block_size, dims[-1].block_size


def check_halo_window(
    jaxpr,
    *,
    location: str,
    spec,
    nms: bool,
    block_h: int,
    block_w: int,
    image_hw: Optional[Tuple[int, int]] = None,
    plan=None,
) -> List[Violation]:
    """HALO001: every grid step's input window — recovered by evaluating
    its ``pl.Element`` index map — covers the block plus
    ``window_radius(spec.radius, nms)`` (with ``plan``:
    ``window_radius(plan.linear_reach, plan.nms)``, the composed reach of
    the fused stage chain) clipped to the image, and the sharded halo
    exchange is sized identically.

    Windows are tile-aligned, so they may reach further than the stencil;
    the reported reach is the margin around interior grid step
    (k, j) = (1, 1), and the first/last steps are probed too, where the
    clamp in :func:`repro.kernels.tiling.window_origin` is active.
    Requires a grid of at least 3×3 blocks (AnalysisError otherwise: that
    is a misconfigured sweep, not an engine bug).
    """
    from repro.kernels.tiling import window_radius, window_shape
    from repro.sharding import halo as halo_mod

    if plan is not None:
        expected = window_radius(plan.linear_reach, nms or plan.nms)
        src = f"linear_reach={plan.linear_reach}, nms={nms or plan.nms}"
    else:
        expected = window_radius(spec.radius, nms)
        src = f"radius={spec.radius}, nms={nms}"
    out: List[Violation] = []

    def vio(message, *detail):
        out.append(Violation("HALO001", location, message, detail=detail))

    for pc in find_pallas_eqns(jaxpr):
        gm = pc.params["grid_mapping"]
        grid = tuple(gm.grid)
        if len(grid) != 3:
            raise AnalysisError(f"expected (n, gh, gw) grid, got {grid}")
        if grid[1] < 3 or grid[2] < 3:
            raise AnalysisError(
                f"grid {grid} too small to probe an interior block; "
                "use an image of at least 3x3 blocks"
            )
        windows = 0
        for bm in gm.block_mappings:
            tile = _element_window(bm)
            if tile is None or tile[0] <= block_h:
                continue  # not a halo'd input window
            windows += 1
            th, tw = tile
            h, w = bm.array_aval.shape[-2:]
            offs = _eval_index_map(bm, (0, 1, 1))
            row0, col0 = offs[-2], offs[-1]
            r_h = min(block_h - row0, row0 + th - 2 * block_h)
            r_w = min(block_w - col0, col0 + tw - 2 * block_w)
            if r_h < expected or r_w < expected:
                vio(f"kernel window reach ({r_h}, {r_w}) < "
                    f"window_radius({src}) = {expected}",
                    ("derived", f"({r_h}, {r_w})"),
                    ("expected", str(expected)))
                continue
            for k, j in ((0, 0), (grid[1] - 1, grid[2] - 1)):
                offs = _eval_index_map(bm, (0, k, j))
                row0, col0 = offs[-2], offs[-1]
                need_r = (max(k * block_h - expected, 0),
                          min((k + 1) * block_h + expected, h))
                need_c = (max(j * block_w - expected, 0),
                          min((j + 1) * block_w + expected, w))
                if not (row0 <= need_r[0] and need_r[1] <= row0 + th
                        and col0 <= need_c[0] and need_c[1] <= col0 + tw):
                    vio(f"window at grid step ({k}, {j}) "
                        f"[{row0}:{row0 + th}, {col0}:{col0 + tw}] misses "
                        f"the r={expected} stencil rows {need_r} / cols "
                        f"{need_c}",
                        ("step", f"({k}, {j})"),
                        ("expected", str(expected)))
            if image_hw is not None:
                want = window_shape(image_hw[0], image_hw[1], block_h,
                                    block_w, expected)
                if tile != want:
                    vio(f"window tile {tile} != window_shape(...) = {want} "
                        f"for r={expected}",
                        ("tile", str(tile)), ("expected", str(want)))
        if not windows:
            vio("no halo'd Element input window on the pallas_call — the "
                "stencil cannot be reading its halo",
                ("windows", "0"))
        exch = halo_mod.exchange_radius(spec, nms, plan=plan)
        if exch != expected:
            vio(f"sharded exchange width {exch} != kernel window radius "
                f"{expected}",
                ("exchange", str(exch)), ("expected", str(expected)))
    return out


def check_static_registration(cls, *, location: str) -> List[Violation]:
    """DET003 (runtime half): a class registered static with JAX must be
    a frozen dataclass — hashable and equal by value — or jit caching on
    it silently degrades (or crashes on unhashable instances). The AST
    half of this rule (``repro.analysis.ast_rules``) catches the same
    mistake in source without importing it."""
    out: List[Violation] = []
    params = getattr(cls, "__dataclass_params__", None)
    if params is None or not params.frozen:
        out.append(
            Violation(
                "DET003",
                location,
                f"{cls.__name__} is registered static but is not a frozen "
                "dataclass",
                detail=(("class", cls.__name__),),
            )
        )
    elif getattr(cls, "__hash__", None) is None:
        out.append(
            Violation(
                "DET003",
                location,
                f"{cls.__name__} is registered static but unhashable",
                detail=(("class", cls.__name__),),
            )
        )
    return out
