"""Registry sweep for the kernel contract analyzer.

Enumerates operator × backend × padding × layout × output-mode combos,
traces each through the public ``repro.api`` surface (no execution —
``jax.make_jaxpr`` / ``jax.export`` only), and runs every applicable
rule from :mod:`repro.analysis.rules`. Adds spec-level checks (dtype
ladder, default-block VMEM, static registration) per operator, a
multi-stage StencilPlan battery (plan × backend × padding: one-launch
FUSE002, composed-reach HALO001/VMEM001), and the AST determinism scan
over the kernel-math sources.

Fast sweep (default): two operators, reflect padding — enough to catch
an engine regression in seconds. Full sweep (``--all`` / ``full=True``):
every registered operator, all paddings on the plain/NMS paths, plus the
TPU Mosaic export battery; this is what CI's ``analysis`` job runs and
what the acceptance gate means by "the clean tree".
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import export as jax_export

from repro.analysis import ast_rules, rules
from repro.analysis.violations import Report, Violation

__all__ = ["analyze", "MODES", "kernel_math_files", "DEFAULT_OPERATORS",
           "DEFAULT_PLANS"]

# Trace geometry: >= 3 blocks per axis so HALO001 can probe an interior
# grid step (see rules.check_halo_window).
TRACE_SHAPE = (1, 64, 96)
TRACE_BLOCK = (16, 32)

# Export geometry: Mosaic wants lane-aligned tiles; this matches the
# fused-pipeline spy tests.
EXPORT_SHAPE = (1, 512, 640)
EXPORT_BLOCK = (64, 128)

DEFAULT_OPERATORS = ("sobel3", "sobel5")
DEFAULT_PLANS = ("canny5", "blur_sobel5")
BACKENDS = ("xla", "pallas-interpret")
PAD_MODES = ("reflect", "edge", "zero")

# Representative service resolutions for the default-block VMEM check.
SERVICE_SHAPES = ((512, 640), (1080, 1920), (2160, 3840))


@dataclasses.dataclass(frozen=True)
class Mode:
    """One output mode of the engine and how the rules apply to it."""

    name: str
    config_kw: Tuple[Tuple[str, object], ...] = ()
    stream: bool = False
    unstack: bool = False  # FUSE001 component-unstack allowance
    opaque_while: bool = False  # hysteresis: post-gather fixpoint pads by design
    all_paddings: bool = False  # sweep every padding in full mode
    export: bool = False  # part of the Mosaic export battery
    gray_only: bool = False  # integer lane: RGB is ineligible by design
    frame_dtype: str = "uint8"  # dtype of the traced frames

    def kw(self) -> Dict[str, object]:
        return dict(self.config_kw)


MODES: Dict[str, Mode] = {
    m.name: m
    for m in [
        Mode("plain", (), all_paddings=True, export=True),
        Mode("nms", (("nms", True),), all_paddings=True, export=True),
        Mode("components", (("with_components", True),), unstack=True),
        Mode("orientation", (("with_orientation", True),), unstack=True),
        Mode("hysteresis", (("hysteresis", True),), opaque_while=True),
        Mode("stream", (), stream=True),
        Mode("stream-nms", (("nms", True),), stream=True),
        Mode("lowprec", (("precision", "int"),), gray_only=True, export=True),
        # 12/16-bit sensor frames read raw: the integer lane at the u16
        # bound (i32 for sobel5), traced only where the ladder admits it.
        Mode("lowprec-u16", (("precision", "int"),), gray_only=True,
             export=True, frame_dtype="uint16"),
    ]
}

# Kernel-math modules excluded from the determinism scan, with reasons.
_DET_EXCLUDE = {
    # The autotuner measures wall-clock on purpose; it feeds the cache,
    # never a kernel.
    "kernels/tuning.py",
}


def kernel_math_files() -> List[Tuple[str, str]]:
    """(abspath, repo-relative path) of every kernel-math source file."""
    import repro

    pkg = os.path.dirname(os.path.abspath(repro.__file__))
    out: List[Tuple[str, str]] = []
    for sub in ("core", "kernels"):
        d = os.path.join(pkg, sub)
        for fn in sorted(os.listdir(d)):
            if not fn.endswith(".py"):
                continue
            rel = f"{sub}/{fn}"
            if rel in _DET_EXCLUDE:
                continue
            out.append((os.path.join(d, fn), f"src/repro/{rel}"))
    return out


def _all_repro_files() -> List[Tuple[str, str]]:
    import repro

    pkg = os.path.dirname(os.path.abspath(repro.__file__))
    out: List[Tuple[str, str]] = []
    for root, _dirs, files in os.walk(pkg):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            ap = os.path.join(root, fn)
            rel = os.path.relpath(ap, os.path.dirname(pkg))
            out.append((ap, f"src/{rel}"))
    return out


def _trace_combo(op: str, backend: str, padding: str, layout: str, mode: Mode):
    """ClosedJaxpr of one combo through the public API (trace only)."""
    from repro import api

    cfg = api.EdgeConfig(
        operator=op,
        backend=backend,
        padding=padding,
        block_h=TRACE_BLOCK[0],
        block_w=TRACE_BLOCK[1],
        **mode.kw(),
    )
    rgb = layout == "rgb"
    n, h, w = TRACE_SHAPE
    shape = (n, h, w, 3) if rgb else (n, h, w)
    x = jnp.zeros(shape, mode.frame_dtype)
    if mode.stream:
        state = api.StreamState.init(n, h, w, cfg, rgb=rgb)
        jaxpr = jax.make_jaxpr(lambda f, s: api.edge_detect_stream(f, cfg, s))(
            x, state
        )
    else:
        jaxpr = jax.make_jaxpr(lambda a: api.edge_detect(a, cfg))(x)
    return jaxpr, cfg


def _combo_violations(
    op: str, backend: str, padding: str, layout: str, mode: Mode, report: Report
) -> List[Violation]:
    from repro.core.filters import get_operator

    location = f"{op}/{backend}/{padding}/{layout}/{mode.name}"
    jaxpr, _cfg = _trace_combo(op, backend, padding, layout, mode)
    report.combos.append(location)
    spec = get_operator(op)
    nms = bool(mode.kw().get("nms") or mode.kw().get("hysteresis"))
    out: List[Violation] = []

    fused = backend.startswith("pallas")
    if fused:
        opaque = ("pallas_call",) + (("while",) if mode.opaque_while else ())
        out += rules.check_fusion_purity(
            jaxpr, location=location, allow_unstack=mode.unstack, opaque=opaque
        )
        out += rules.check_kernel_cardinality(jaxpr, location=location)
        report.checks += 2
        if not mode.stream:
            out += rules.check_halo_window(
                jaxpr,
                location=location,
                spec=spec,
                nms=nms,
                block_h=TRACE_BLOCK[0],
                block_w=TRACE_BLOCK[1],
                image_hw=TRACE_SHAPE[1:],
            )
            out += rules.check_vmem_budget(
                location=location,
                block_h=TRACE_BLOCK[0],
                block_w=TRACE_BLOCK[1],
                radius=spec.radius,
                nms=nms,
                channels=3 if layout == "rgb" else None,
            )
            report.checks += 2
    # Vacuous on f32-lane traces; on the integer lane (either backend) it
    # pins the actual accumulation dtype to the ladder proof.
    out += rules.check_kernel_accum_dtype(jaxpr, location=location, spec=spec)
    out += rules.check_contraction_fences(jaxpr, location=location)
    report.checks += 2
    return out


def _plan_violations(
    plan_name: str, backend: str, padding: str, report: Report
) -> List[Violation]:
    """Multi-stage StencilPlan battery: the whole plan (pre-stages →
    gradient → optional NMS) must trace as ONE pallas_call (FUSE002 with
    ``expected=1`` — the tentpole claim of the stencil platform), with the
    *composed* halo (``plan.linear_reach`` + NMS ring) on the kernel
    window, the VMEM budget, and the sharded exchange width."""
    from repro import api
    from repro.core.filters import get_plan

    plan = get_plan(plan_name)
    location = f"plan:{plan_name}/{backend}/{padding}/gray"
    cfg = api.EdgeConfig(
        plan=plan_name,
        backend=backend,
        padding=padding,
        block_h=TRACE_BLOCK[0],
        block_w=TRACE_BLOCK[1],
    )
    x = jnp.zeros(TRACE_SHAPE, jnp.uint8)
    jaxpr = jax.make_jaxpr(lambda a: api.edge_detect(a, cfg))(x)
    report.combos.append(location)
    spec = plan.gradient
    out: List[Violation] = []
    if backend.startswith("pallas"):
        out += rules.check_fusion_purity(jaxpr, location=location)
        out += rules.check_kernel_cardinality(jaxpr, location=location,
                                              expected=1)
        out += rules.check_halo_window(
            jaxpr,
            location=location,
            spec=spec,
            nms=plan.nms,
            block_h=TRACE_BLOCK[0],
            block_w=TRACE_BLOCK[1],
            image_hw=TRACE_SHAPE[1:],
            plan=plan,
        )
        out += rules.check_vmem_budget(
            location=location,
            block_h=TRACE_BLOCK[0],
            block_w=TRACE_BLOCK[1],
            radius=spec.radius,
            nms=plan.nms,
            plan=plan,
        )
        report.checks += 4
    out += rules.check_kernel_accum_dtype(jaxpr, location=location, spec=spec)
    out += rules.check_contraction_fences(jaxpr, location=location)
    report.checks += 2
    return out


def _export_violations(op: str, layout: str, mode: Mode, report: Report) -> List[Violation]:
    """FUSE003 over the real Mosaic lowering (cross-platform TPU export;
    runs fine on CPU hosts — nothing executes)."""
    from repro import api

    location = f"{op}/tpu-export/{layout}/{mode.name}"
    n, h, w = EXPORT_SHAPE
    rgb = layout == "rgb"
    shape = (n, h, w, 3) if rgb else (n, h, w)
    cfg = api.EdgeConfig(
        operator=op,
        backend="pallas-tpu",
        block_h=EXPORT_BLOCK[0],
        block_w=EXPORT_BLOCK[1],
        **mode.kw(),
    )
    x = jnp.zeros(shape, mode.frame_dtype)
    try:
        exported = jax_export.export(
            jax.jit(lambda a: api.edge_detect(a, cfg).magnitude), platforms=["tpu"]
        )(x)
        mlir = exported.mlir_module()
    except Exception as e:
        report.combos.append(location)
        report.checks += 1
        return [
            Violation(
                "FUSE003",
                location,
                f"TPU export failed: {type(e).__name__}: {e}",
                detail=(("error", type(e).__name__),),
            )
        ]
    report.combos.append(location)
    report.checks += 1
    return rules.check_mosaic_program(mlir, location=location)


def _spec_violations(op: str, report: Report) -> List[Violation]:
    from repro.core import ladder
    from repro.core.filters import get_operator
    from repro.kernels.edge import default_block_shape

    spec = get_operator(op)
    out: List[Violation] = []
    location = f"spec:{op}"
    for frame in ladder.INT_LANE_INPUTS:
        out += rules.check_dtype_ladder(spec, location=location,
                                        input_dtype=frame)
        report.checks += 1
    # The fallback block chooser must respect the budget it was derived
    # from, at every service resolution, worst-case halo (NMS) included.
    for h, w in SERVICE_SHAPES:
        for channels in (None, 3):
            bh, bw = default_block_shape(h, w, spec.size, channels=channels)
            out += rules.check_vmem_budget(
                location=f"{location}/default-block-{h}x{w}"
                + ("-rgb" if channels else ""),
                block_h=bh,
                block_w=bw,
                radius=spec.radius,
                nms=True,
                channels=channels,
            )
            report.checks += 1
    report.combos.append(location)
    return out


def _static_violations(report: Report) -> List[Violation]:
    """Runtime half of DET003 on the engine's registered-static classes."""
    from repro.api import EdgeConfig
    from repro.core.filters import OperatorSpec

    out: List[Violation] = []
    for cls, location in (
        (OperatorSpec, "class:repro.core.filters.OperatorSpec"),
        (EdgeConfig, "class:repro.api.EdgeConfig"),
    ):
        out += rules.check_static_registration(cls, location=location)
        report.checks += 1
    return out


def _source_violations(report: Report) -> List[Violation]:
    out: List[Violation] = []
    kernel_math = set()
    for ap, rel in kernel_math_files():
        kernel_math.add(rel)
        out += ast_rules.scan_file(ap, rel=rel)
        report.checks += 3
    # Repo-wide DET003: register_static must target frozen dataclasses
    # everywhere, not just in kernel math.
    for ap, rel in _all_repro_files():
        if rel in kernel_math:
            continue
        vs = ast_rules.scan_file(ap, rel=rel, rules=("DET003",))
        out += vs
        report.checks += 1
    return out


def _lane_admits(op: str, mode: Mode) -> bool:
    """False where ``mode`` asks for the integer lane on frames whose bound
    the ladder refuses for ``op`` (sobel7 at u16)."""
    from repro.core import ladder
    from repro.core.filters import get_operator

    if dict(mode.config_kw).get("precision") != "int":
        return True
    ok, _reason = ladder.int_lane_eligible(
        get_operator(op), rgb=False, input_dtype=mode.frame_dtype
    )
    return ok


def analyze(
    *,
    operators: Optional[Sequence[str]] = None,
    backends: Optional[Sequence[str]] = None,
    paddings: Optional[Sequence[str]] = None,
    modes: Optional[Sequence[str]] = None,
    layouts: Optional[Sequence[str]] = None,
    plans: Optional[Sequence[str]] = None,
    export: bool = True,
    full: bool = False,
) -> Report:
    """Run the analyzer sweep; returns a :class:`Report` (no baseline
    applied — the CLI handles that)."""
    from repro.core.filters import list_operators, list_plans

    if operators is None:
        operators = tuple(list_operators()) if full else DEFAULT_OPERATORS
    if plans is None:
        plans = tuple(list_plans()) if full else DEFAULT_PLANS
    backends = tuple(backends or BACKENDS)
    paddings = tuple(paddings or (PAD_MODES if full else ("reflect",)))
    mode_names = tuple(modes or MODES)
    layouts = tuple(layouts or ("gray", "rgb"))

    report = Report(meta={"full": full, "operators": list(operators),
                          "plans": list(plans)})
    for op in operators:
        for layout in layouts:
            # RGB exercises the in-kernel luma path, which is operator-
            # independent — one operator covers it.
            if layout == "rgb" and op != operators[0]:
                continue
            for backend in backends:
                for mode_name in mode_names:
                    mode = MODES[mode_name]
                    if mode.stream and backend == "xla":
                        continue  # streaming is a fused-path feature
                    if mode.gray_only and layout == "rgb":
                        continue  # explicit int on RGB raises by contract
                    if not _lane_admits(op, mode):
                        continue  # explicit int past the bound raises too
                    pads = paddings if (mode.all_paddings or not full) else ("reflect",)
                    if not mode.all_paddings:
                        pads = pads[:1]
                    for padding in pads:
                        report.add(
                            _combo_violations(
                                op, backend, padding, layout, mode, report
                            )
                        )
    for plan_name in plans:
        for backend in backends:
            for padding in paddings:
                report.add(_plan_violations(plan_name, backend, padding, report))
    if export:
        for op in operators if full else operators[:1]:
            for mode_name in mode_names:
                mode = MODES[mode_name]
                if not mode.export or not _lane_admits(op, mode):
                    continue
                report.add(_export_violations(op, "gray", mode, report))
        for mode_name in mode_names:
            mode = MODES[mode_name]
            if mode.export and not mode.gray_only and "rgb" in layouts:
                report.add(_export_violations(operators[0], "rgb", mode, report))
    for op in operators:
        report.add(_spec_violations(op, report))
    report.add(_static_violations(report))
    report.add(_source_violations(report))
    return report
