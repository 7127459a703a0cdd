"""Kernel contract analyzer battery.

Two halves:

* golden *known-bad* artifacts — a deliberately padded pipeline, an
  unfenced mul+add chain, an oversized VMEM block, an off-by-one halo
  window, an unfrozen register_static pytree, an over-range integer tap
  bank — each must trigger exactly its own rule ID and nothing else
  when run through the full applicable rule set;
* report plumbing — JSON shape snapshot, human table, baseline
  round-trip, CLI exit codes.

The *clean-tree* direction (every rule passing on the real engine) is
covered by the CI ``analysis`` job (``python -m repro.analysis --all``)
and by the fast-sweep smoke test at the bottom.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro import analysis
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.violations import Report, Violation
from repro.core.filters import get_operator, make_separable_spec
from repro.kernels import edge as ekern
from repro.kernels.tiling import window_spec


def _all_trace_rules(
    jaxpr,
    *,
    spec,
    nms=False,
    block_h=16,
    block_w=32,
    image_hw=(64, 96),
    channels=None,
    allow_unstack=False,
    opaque=("pallas_call",),
):
    """The full fused-path rule set, exactly as the sweep applies it."""
    loc = "test"
    vios = []
    vios += analysis.check_fusion_purity(
        jaxpr, location=loc, allow_unstack=allow_unstack, opaque=opaque
    )
    vios += analysis.check_kernel_cardinality(jaxpr, location=loc)
    vios += analysis.check_contraction_fences(jaxpr, location=loc)
    vios += analysis.check_halo_window(
        jaxpr,
        location=loc,
        spec=spec,
        nms=nms,
        block_h=block_h,
        block_w=block_w,
        image_hw=image_hw,
    )
    vios += analysis.check_vmem_budget(
        location=loc,
        block_h=block_h,
        block_w=block_w,
        radius=spec.radius,
        nms=nms,
        channels=channels,
    )
    return vios


def _rule_ids(vios):
    return {v.rule for v in vios}


# ---------------------------------------------------------------------------
# Clean reference: the real fused kernel passes the full rule set
# ---------------------------------------------------------------------------

def test_clean_fused_kernel_passes_all_rules():
    x = jnp.zeros((1, 64, 96), jnp.uint8)
    jaxpr = jax.make_jaxpr(
        lambda a: ekern.edge_pallas(a, block_h=16, block_w=32, interpret=True)
    )(x)
    assert _all_trace_rules(jaxpr, spec=get_operator("sobel5")) == []


def test_clean_pipelined_int_kernel_passes_all_rules():
    """The manual-DMA + integer-lane kernel satisfies the full rule set,
    including PIPE001 and the ring-based HALO001 probe (no Element
    window exists on the ANY-space input)."""
    spec = get_operator("sobel5")
    x = jnp.zeros((1, 64, 96), jnp.uint8)
    jaxpr = jax.make_jaxpr(
        lambda a: ekern.edge_pallas(
            a, block_h=16, block_w=32, precision="int", pipeline_depth=2,
            interpret=True,
        )
    )(x)
    vios = _all_trace_rules(jaxpr, spec=spec)
    vios += analysis.check_dma_pipeline(jaxpr, location="test")
    vios += analysis.check_kernel_accum_dtype(jaxpr, location="test", spec=spec)
    assert vios == []


# ---------------------------------------------------------------------------
# Golden known-bad battery: each artifact trips exactly its rule
# ---------------------------------------------------------------------------

def test_bad_padded_pipeline_trips_fuse001_only():
    """HBM-side jnp.pad staging + compensating slice around the kernel:
    the exact round-trip PR 2 deleted. Only FUSE001 may fire — the
    kernel itself (halo, fences, budget) is still sound."""
    def bad(x):
        xp = jnp.pad(x, ((0, 0), (2, 2), (2, 2)))  # constant mode -> pad prim
        y = ekern.edge_pallas(xp, block_h=16, block_w=32, interpret=True)
        return jax.lax.slice(y, (0, 2, 2), (1, 66, 98))

    jaxpr = jax.make_jaxpr(bad)(jnp.zeros((1, 64, 96), jnp.uint8))
    vios = _all_trace_rules(
        jaxpr, spec=get_operator("sobel5"), image_hw=(68, 100)
    )
    assert _rule_ids(vios) == {"FUSE001"}
    prims = {dict(v.detail)["primitive"] for v in vios}
    assert prims == {"pad", "slice"}


def test_bad_unfenced_mul_add_trips_fma001_only():
    """A w*x + y tap chain with no maximum() fence — the contraction
    hazard the _tap idiom exists to prevent."""
    def bad(x):
        y = ekern.edge_pallas(x, block_h=16, block_w=32, interpret=True)
        return y * jnp.float32(1.5) + y  # unfenced: mul feeds add directly

    jaxpr = jax.make_jaxpr(bad)(jnp.zeros((1, 64, 96), jnp.uint8))
    vios = _all_trace_rules(jaxpr, spec=get_operator("sobel5"))
    assert _rule_ids(vios) == {"FMA001"}


def test_bad_unfenced_kernel_body_trips_fma001():
    """The fence rule descends into pallas_call bodies — an unfenced
    kernel is flagged even though HBM-level code is clean."""
    def kernel(x_ref, o_ref):
        x = x_ref[...]
        o_ref[...] = jnp.float32(2.0) * x + jnp.float32(3.0) * x

    def bad(x):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
            interpret=True,
        )(x)

    jaxpr = jax.make_jaxpr(bad)(jnp.zeros((8, 128), jnp.float32))
    assert _rule_ids(analysis.check_contraction_fences(jaxpr, location="t")) == {
        "FMA001"
    }
    # ...and the fenced version of the same kernel is clean.
    def fenced_kernel(x_ref, o_ref):
        x = x_ref[...]
        lo = jnp.float32(np.finfo(np.float32).min)
        o_ref[...] = jnp.maximum(jnp.float32(2.0) * x, lo) + jnp.maximum(
            jnp.float32(3.0) * x, lo
        )

    def good(x):
        return pl.pallas_call(
            fenced_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
            interpret=True,
        )(x)

    jaxpr = jax.make_jaxpr(good)(jnp.zeros((8, 128), jnp.float32))
    assert analysis.check_contraction_fences(jaxpr, location="t") == []


def test_bad_oversized_block_trips_vmem001_only():
    """A (512, 4096) block's halo'd working set blows the 16 MiB VMEM
    budget; every other contract (fusion, halo, fences) stays intact."""
    x = jnp.zeros((1, 1536, 12288), jnp.uint8)
    jaxpr = jax.make_jaxpr(
        lambda a: ekern.edge_pallas(a, block_h=512, block_w=4096, interpret=True)
    )(x)
    vios = _all_trace_rules(
        jaxpr,
        spec=get_operator("sobel5"),
        block_h=512,
        block_w=4096,
        image_hw=(1536, 12288),
    )
    assert _rule_ids(vios) == {"VMEM001"}


def test_bad_off_by_one_halo_trips_halo001_only():
    """A kernel compiled with an r=1 window while the operator needs
    r=2: the exact off-by-one the index-map probe exists to catch. (The
    repo's own tile-aligned windows carry slack, so the bad window is an
    exact-fit Element window written by hand.)"""
    h, w, bh, bw = 64, 96, 16, 32

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[:, 1:17, 1:33].astype(jnp.float32)

    def origin(i, k, j):
        return (i, jnp.clip(k * bh - 1, 0, h - bh - 2),
                jnp.clip(j * bw - 1, 0, w - bw - 2))

    r1_window = pl.BlockSpec(
        (pl.Element(1), pl.Element(bh + 2), pl.Element(bw + 2)), origin
    )

    def bad(x):
        return pl.pallas_call(
            kernel,
            grid=(1, h // bh, w // bw),
            in_specs=[r1_window],  # sobel5 needs r=2
            out_specs=pl.BlockSpec((1, bh, bw), lambda i, k, j: (i, k, j)),
            out_shape=jax.ShapeDtypeStruct((1, h, w), jnp.float32),
            interpret=True,
        )(x)

    jaxpr = jax.make_jaxpr(bad)(jnp.zeros((1, h, w), jnp.uint8))
    vios = _all_trace_rules(jaxpr, spec=get_operator("sobel5"))
    assert _rule_ids(vios) == {"HALO001"}
    assert "window reach (1, 1)" in vios[0].message


def test_bad_unfrozen_static_pytree_trips_det003_only():
    """register_static on an unfrozen dataclass: unhashable the moment
    jit uses it as a static argument. Caught both at runtime and in
    source, without firing the other determinism rules."""

    @dataclasses.dataclass
    class BadConfig:
        a: int = 1

    vios = analysis.check_static_registration(BadConfig, location="t")
    assert _rule_ids(vios) == {"DET003"}

    snippet = (
        "import dataclasses\n"
        "import jax\n"
        "\n"
        "@dataclasses.dataclass\n"
        "class BadConfig:\n"
        "    a: int = 1\n"
        "\n"
        "jax.tree_util.register_static(BadConfig)\n"
    )
    vios = analysis.scan_source(snippet, "bad_config.py")
    assert _rule_ids(vios) == {"DET003"}
    # The frozen version is clean.
    good = snippet.replace("@dataclasses.dataclass", "@dataclasses.dataclass(frozen=True)")
    assert analysis.scan_source(good, "good_config.py") == []


def test_bad_over_range_integer_taps_trip_dtype001_only():
    """Integer taps whose u8 accumulation exceeds 2^24 cannot claim the
    exact-f32 contract the engine (and the future low-precision kernel)
    relies on."""
    spec = make_separable_spec(
        "huge", [256, 256, 256, 256, 256], [-64, -32, 0, 32, 64]
    )
    vios = analysis.check_dtype_ladder(spec, location="spec:huge")
    vios += analysis.check_static_registration(type(spec), location="spec:huge")
    assert _rule_ids(vios) == {"DTYPE001"}
    b = analysis.tap_accumulation_bounds(spec)
    assert b["integer_taps"] and not b["f32_exact"]
    # Every *registered* operator holds the contract, with headroom facts
    # the low-precision kernel will cite.
    for name in ("sobel3", "sobel5", "scharr3", "prewitt3", "sobel7"):
        bounds = analysis.tap_accumulation_bounds(get_operator(name))
        assert bounds["integer_taps"] and bounds["f32_exact"], (name, bounds)
        assert bounds["fits_i32"], name


def _toy_pipelined_jaxpr(*, wait=True, depth=2, sem_depth=None):
    """A minimal manual-DMA pallas_call: ANY-space input, one ring slot
    copied per grid step. Knobs deliberately break the PIPE001 contract."""
    from jax.experimental.pallas import tpu as pltpu

    h, w, bh, bw = 64, 96, 16, 32
    sem_depth = depth if sem_depth is None else sem_depth

    def kernel(x_hbm, o_ref, buf, sem):
        i, k, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        cp = pltpu.make_async_copy(
            x_hbm.at[i, pl.ds(k * bh, bh), pl.ds(j * bw, bw)],
            buf.at[0],
            sem.at[0],
        )
        cp.start()
        if wait:
            cp.wait()
        o_ref[...] = buf[0].astype(jnp.float32)[None]

    def run(x):
        from jax.experimental.pallas import tpu as pltpu

        return pl.pallas_call(
            kernel,
            grid=(1, h // bh, w // bw),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, bh, bw), lambda i, k, j: (i, k, j)),
            out_shape=jax.ShapeDtypeStruct((1, h, w), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((depth, bh, bw), jnp.uint8),
                pltpu.SemaphoreType.DMA((sem_depth,)),
            ],
            interpret=True,
        )(x)

    return jax.make_jaxpr(run)(jnp.zeros((1, h, w), jnp.uint8))


def test_bad_dma_start_without_wait_trips_pipe001_only():
    """A started copy that is never waited on: the consumer races the
    DMA engine. PIPE001 must flag it; no other rule fires."""
    jaxpr = _toy_pipelined_jaxpr(wait=False)
    vios = analysis.check_dma_pipeline(jaxpr, location="t")
    assert _rule_ids(vios) == {"PIPE001"}
    assert "no dma_wait" in vios[0].message
    # The same kernel with the wait restored is PIPE001-clean.
    assert analysis.check_dma_pipeline(_toy_pipelined_jaxpr(), location="t") == []


def test_bad_single_slot_ring_trips_pipe001():
    """depth=1 means the compute phase always blocks on the copy it just
    issued — no overlap, no pipeline. The depth floor is 2."""
    vios = analysis.check_dma_pipeline(_toy_pipelined_jaxpr(depth=1), location="t")
    assert _rule_ids(vios) == {"PIPE001"}
    assert any("depth 1 < 2" in v.message for v in vios)


def test_bad_semaphore_ring_mismatch_trips_pipe001():
    """One semaphore shared by two ring slots: waits cannot pair with
    starts per slot, so back-to-back copies serialize (or worse)."""
    vios = analysis.check_dma_pipeline(
        _toy_pipelined_jaxpr(depth=2, sem_depth=1), location="t"
    )
    assert _rule_ids(vios) == {"PIPE001"}
    assert "1 DMA semaphore(s) for a depth-2 ring" in vios[0].message


def test_bad_narrow_accumulation_trips_dtype001_only():
    """A trace that accumulates sobel5 taps in i16 — the ladder proves
    the v2 pairwise bound needs i32, so i16 wraps. The kernel half of
    DTYPE001 catches what the spec half cannot see."""
    spec5 = get_operator("sobel5")

    def bad(x):
        return (x.astype(jnp.int16) * 2).astype(jnp.float32)

    jaxpr = jax.make_jaxpr(bad)(jnp.zeros((1, 64, 96), jnp.uint8))
    vios = analysis.check_kernel_accum_dtype(jaxpr, location="t", spec=spec5)
    assert _rule_ids(vios) == {"DTYPE001"}
    assert "accumulates u8 taps in int16" in vios[0].message

    # The licensed dtype is clean; wider-than-licensed stays exact and
    # is clean too (the TPU lane widens sobel3's i16 around Mosaic gaps).
    def i32(x):
        return (x.astype(jnp.int32) * 2).astype(jnp.float32)

    jaxpr32 = jax.make_jaxpr(i32)(jnp.zeros((1, 64, 96), jnp.uint8))
    assert analysis.check_kernel_accum_dtype(jaxpr32, location="t", spec=spec5) == []
    assert analysis.check_kernel_accum_dtype(
        jaxpr32, location="t", spec=get_operator("sobel3")
    ) == []
    # An f32-lane trace (no u8 -> int cast anywhere) passes vacuously.
    jaxpr_f32 = jax.make_jaxpr(lambda x: x.astype(jnp.float32) * 2.0)(
        jnp.zeros((1, 64, 96), jnp.uint8)
    )
    assert analysis.check_kernel_accum_dtype(
        jaxpr_f32, location="t", spec=spec5
    ) == []


def test_dtype001_covers_u16_frames(monkeypatch):
    """Both halves at the u16 bound (65535). The spec half: the integer
    lane admits an operator's u16 frames exactly where its bound there is
    f32-exact and fits i32 (sobel5 yes, sobel7 no). The kernel half: the
    traced u16 integer lane of sobel5 enters i32; an i16 entry wraps; the
    f32 lane's widening through i32 is no entry."""
    from repro.core import ladder

    for name in ("sobel3", "sobel5", "scharr3", "prewitt3", "sobel7"):
        spec = get_operator(name)
        assert analysis.check_dtype_ladder(
            spec, location="t", input_dtype="uint16") == [], name
    assert not analysis.tap_accumulation_bounds(
        get_operator("sobel7"), input_max=65535)["f32_exact"]
    # a gate that admitted every operator would let sobel7's u16 frames in
    monkeypatch.setattr(ladder, "int_lane_eligible", lambda *a, **k: (True, ""))
    vios = analysis.check_dtype_ladder(get_operator("sobel7"), location="t",
                                       input_dtype="uint16")
    assert _rule_ids(vios) == {"DTYPE001"} and "admits uint16" in vios[0].message
    monkeypatch.undo()

    spec5, spec7 = get_operator("sobel5"), get_operator("sobel7")
    x16 = jnp.zeros((1, 64, 96), jnp.uint16)
    lane = jax.make_jaxpr(lambda a: ekern.edge_pallas(
        a, block_h=16, block_w=32, precision="int", interpret=True))(x16)
    assert analysis.check_kernel_accum_dtype(lane, location="t", spec=spec5) == []
    f32_lane = jax.make_jaxpr(lambda a: ekern.edge_pallas(
        a, operator="sobel7", block_h=16, block_w=32, interpret=True))(x16)
    assert analysis.check_kernel_accum_dtype(
        f32_lane, location="t", spec=spec7) == []

    narrow = jax.make_jaxpr(
        lambda a: (a.astype(jnp.int16) * 2).astype(jnp.float32))(x16)
    vios = analysis.check_kernel_accum_dtype(narrow, location="t", spec=spec5)
    assert _rule_ids(vios) == {"DTYPE001"}
    assert "accumulates u16 taps in int16" in vios[0].message
    wide = jax.make_jaxpr(
        lambda a: (a.astype(jnp.int32) * 2).astype(jnp.float32))(x16)
    vios = analysis.check_kernel_accum_dtype(wide, location="t", spec=spec7)
    assert _rule_ids(vios) == {"DTYPE001"} and "no proven" in vios[0].message


def test_bad_wrong_radius_ring_trips_halo001():
    """HALO001's ring branch: a manual-DMA kernel whose ring slots are
    sized for r=1 cannot be feeding an r=2 stencil — probed against the
    sobel3-pipelined trace under the sobel5 contract. (block_h=14: a
    height at which the tile-aligned r=1 and r=2 windows differ.)"""
    x = jnp.zeros((1, 64, 96), jnp.uint8)
    jaxpr = jax.make_jaxpr(
        lambda a: ekern.edge_pallas(
            a, operator="sobel3", block_h=14, block_w=32, pipeline_depth=2,
            interpret=True,
        )
    )(x)
    vios = analysis.check_halo_window(
        jaxpr, location="t", spec=get_operator("sobel5"), nms=False,
        block_h=14, block_w=32, image_hw=(64, 96),
    )
    assert _rule_ids(vios) == {"HALO001"}
    assert "DMA ring slot tile" in vios[0].message
    # ...and under its own (sobel3) contract the same trace is clean.
    assert analysis.check_halo_window(
        jaxpr, location="t", spec=get_operator("sobel3"), nms=False,
        block_h=14, block_w=32, image_hw=(64, 96),
    ) == []


# ---------------------------------------------------------------------------
# Determinism source rules (DET001/DET002)
# ---------------------------------------------------------------------------

def test_det001_wall_clock_and_randomness():
    src = (
        "import time\n"
        "import numpy as np\n"
        "def f():\n"
        "    t = time.perf_counter()\n"
        "    return np.random.default_rng().normal() + t\n"
    )
    vios = analysis.scan_source(src, "m.py")
    assert _rule_ids(vios) == {"DET001"}
    assert len(vios) == 3  # the import, the clock call, the RNG call


def test_det002_python_branch_on_tracer():
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def f(x, taps):\n"
        "    if np.any(taps):\n"          # static host data: fine
        "        x = x + 1\n"
        "    if jnp.any(x > 0):\n"        # traced: concretization error
        "        x = x * 2\n"
        "    while jnp.max(x) > 1:\n"     # traced: DET002
        "        x = x / 2\n"
        "    n = x.reshape(-1) if jnp.ndim(x) > 2 else x\n"  # static query: fine
        "    return n\n"
    )
    vios = analysis.scan_source(src, "m.py")
    assert _rule_ids(vios) == {"DET002"}
    assert len(vios) == 2
    assert {dict(v.detail)["call"] for v in vios} == {"jax.numpy.any", "jax.numpy.max"}


# ---------------------------------------------------------------------------
# Component-unstack allowance: scoped, not a blanket slice pass
# ---------------------------------------------------------------------------

def test_unstack_allowance_is_scoped():
    from repro import api

    cfg = api.EdgeConfig(
        operator="sobel5", backend="pallas-interpret", block_h=16, block_w=32,
        with_components=True,
    )
    x = jnp.zeros((1, 64, 96), jnp.uint8)
    jaxpr = jax.make_jaxpr(lambda a: api.edge_detect(a, cfg))(x)
    # Without the allowance the unstack slices are (correctly) flagged...
    flagged = analysis.check_fusion_purity(jaxpr, location="t")
    assert _rule_ids(flagged) == {"FUSE001"}
    # ...with it, the path is clean — but only slices of the exact
    # (N, D, H, W) -> (N, 1, H, W) plane-peel signature are excused.
    assert analysis.check_fusion_purity(jaxpr, location="t", allow_unstack=True) == []


# ---------------------------------------------------------------------------
# Report format snapshot + baseline round-trip + CLI
# ---------------------------------------------------------------------------

def _toy_report():
    r = Report(checks=7, combos=["a/b", "c/d"])
    r.add(
        [
            Violation("FUSE001", "c/d", "1 HBM-level `pad` op(s) in a fused path",
                      detail=(("count", "1"), ("primitive", "pad"))),
            Violation("FMA001", "a/b", "unfenced float mul feeding add"),
        ]
    )
    return r


def test_report_json_snapshot():
    got = _toy_report().to_json_dict()
    assert got == {
        "version": 1,
        "ok": False,
        "checks": 7,
        "combos": ["a/b", "c/d"],
        "summary": {"FMA001": 1, "FUSE001": 1},
        "violations": [
            {
                "rule": "FMA001",
                "location": "a/b",
                "message": "unfenced float mul feeding add",
                "detail": {},
            },
            {
                "rule": "FUSE001",
                "location": "c/d",
                "message": "1 HBM-level `pad` op(s) in a fused path",
                "detail": {"count": "1", "primitive": "pad"},
            },
        ],
        "allowlisted": [],
        "meta": {},
    }
    # Round-trips through JSON and back to Violation objects.
    v = Violation.from_dict(json.loads(json.dumps(got["violations"][1])))
    assert v.rule == "FUSE001" and v.fingerprint == "FUSE001|c/d"


def test_report_render_table():
    text = _toy_report().render()
    lines = text.splitlines()
    assert lines[0] == "repro.analysis: 7 checks over 2 artifacts"
    assert "RULE" in lines[1] and "LOCATION" in lines[1]
    assert any(line.lstrip().startswith("FMA001") for line in lines)
    assert lines[-1].startswith("FAIL: 2 new violation(s)")
    clean = Report(checks=3, combos=["x"]).render()
    assert clean.splitlines()[-1] == "OK: no new violations"


def test_baseline_round_trip(tmp_path):
    path = str(tmp_path / "baseline.json")
    report = _toy_report()
    analysis.write_baseline(path, report)
    allow = analysis.load_baseline(path)
    assert set(allow) == {"FUSE001|c/d", "FMA001|a/b"}
    # A fresh run with the same violations is fully suppressed...
    again = _toy_report()
    again.apply_baseline(allow)
    assert again.ok and len(again.allowlisted) == 2
    # ...but a violation at a new location still fails.
    fresh = _toy_report()
    fresh.add([Violation("FUSE001", "new/place", "pad")])
    fresh.apply_baseline(allow)
    assert not fresh.ok and [v.location for v in fresh.violations] == ["new/place"]


def test_rules_table_documented():
    for rule_id, rule in analysis.RULES.items():
        assert rule.id == rule_id
        assert rule.name and rule.guards and rule.since


def test_cli_fast_path_exits_zero(tmp_path, capsys):
    out = str(tmp_path / "report.json")
    rc = analysis_main(
        [
            "--operators", "sobel3",
            "--modes", "plain",
            "--backends", "pallas-interpret",
            "--layouts", "gray",
            "--no-export",
            "--json", out,
        ]
    )
    assert rc == 0
    printed = capsys.readouterr().out
    assert "OK: no new violations" in printed
    data = json.loads(open(out).read())
    assert data["ok"] is True
    assert "sobel3/pallas-interpret/reflect/gray/plain" in data["combos"]


def test_cli_write_baseline(tmp_path):
    path = str(tmp_path / "b.json")
    rc = analysis_main(
        [
            "--operators", "sobel3",
            "--modes", "plain",
            "--backends", "pallas-interpret",
            "--layouts", "gray",
            "--no-export",
            "--write-baseline", path,
        ]
    )
    assert rc == 0
    assert analysis.load_baseline(path) == {}


# ---------------------------------------------------------------------------
# The committed repo baseline stays empty (clean tree)
# ---------------------------------------------------------------------------

def test_committed_baseline_is_clean():
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "analysis_baseline.json")
    assert analysis.load_baseline(path) == {}, (
        "analysis_baseline.json has allowlisted violations — fix them or "
        "document why they must be baselined"
    )
