"""The reader of the program's own spans (``bench/program_trace.py``): on a
described TPU trace whose device intervals and spans are known, and on a
tiny traced run on the CPU."""
import json

import pytest
from jax.profiler import ProfileData

from bench import program_trace as P
from bench import trace as T
from bench.spec import load_module

from _tiny import tiny
from test_trace import PEAKS, TPU_TRACE

_US = 1000000


def _ev(mid, start_us, end_us, stats=""):
    return (f"    events {{ metadata_id: {mid} offset_ps: {int(start_us * _US)} "
            f"duration_ps: {int(round((end_us - start_us) * _US))} {stats}}}\n")


# ``TPU_TRACE`` with the program's spans and JAX's compile events on the
# host line, in us: step 0 [0.5, 4.5) holds stack [0.5, 1), h2d [1, 2.5),
# concat [2.5, 2.8), compute [3, 4.2), split [4.2, 4.4); step 1 [6, 9.8)
# holds stack [6, 6.2), concat [6.2, 6.6), delta [6.6, 7), compute [7, 9)
# (a call to ``step`` [7.1, 8.5) lowering [7.2, 7.6) and compiling
# [7.6, 8.4)), and two splits [9, 9.5) and [9.5, 9.6). A lowering at
# [10.2, 10.4), after the window, is inside no call.
PROGRAM_EVENTS = "".join([
    _ev(10, 0.5, 4.5, "stats { metadata_id: 1 int64_value: 0 } "
        "stats { metadata_id: 2 int64_value: 4 } "
        "stats { metadata_id: 3 int64_value: 1 } "),
    _ev(11, 0.5, 1.0), _ev(12, 1.0, 2.5), _ev(13, 2.5, 2.8),
    _ev(14, 3.0, 4.2), _ev(15, 4.2, 4.4),
    _ev(10, 6.0, 9.8, "stats { metadata_id: 1 int64_value: 1 } "),
    _ev(11, 6.0, 6.2), _ev(13, 6.2, 6.6), _ev(16, 6.6, 7.0),
    _ev(14, 7.0, 9.0), _ev(4, 7.1, 8.5), _ev(17, 7.2, 7.6),
    _ev(18, 7.6, 8.4), _ev(15, 9.0, 9.5), _ev(15, 9.5, 9.6),
    _ev(17, 10.2, 10.4),
])
PROGRAM_METADATA = "".join(
    f'  event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
    for k, n in [(10, "repro.stream.step"), (11, "repro.stream.stack"),
                 (12, "repro.stream.h2d"), (13, "repro.stream.concat"),
                 (14, "repro.stream.compute"), (15, "repro.stream.split"),
                 (16, "repro.stream.delta"),
                 (17, "lower_sharding_computation"),
                 (18, "backend_compile_and_load")]
) + "".join(
    f'  stat_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}\n'
    for k, n in [(1, "step"), (2, "frames"), (3, "groups")]
)
_LAST_EVENT = "    events { metadata_id: 4 offset_ps: 0 duration_ps: 1000000 }\n"
_LAST_METADATA = '  event_metadata { key: 4 value { id: 4 name: "PjitFunction(step)" } }\n'
PROGRAM_TRACE = TPU_TRACE.replace(
    _LAST_EVENT, _LAST_EVENT + PROGRAM_EVENTS
).replace(_LAST_METADATA, _LAST_METADATA + PROGRAM_METADATA)
# Every per-layer reader of the benchmark, with a record each can read.
READERS = (
    "h2d_ms.image", "h2d_ms.stream", "tile_skip_share", "kernel_hbm_roofline",
    "kernel_ms.stream", "xla_device_ms.image", "xla_device_ms.stream",
    "device_idle_share.image", "latency_p95_ms", "device_idle_in_step.stream",
)
RECORD = dict(requests=2, steps=2, min_bytes=500, min_ops=10,
              h2d_s=[0.001, 0.003, 0.002], transfer_ms=[1.0, 5.0, 2.0],
              tiles_per_frame=10, frames_counted=4, skipped_tiles=30,
              latency_s=[0.001 * i for i in range(1, 101)])


def _traces(text):
    data = ProfileData.from_text_proto(text)
    return T.Trace.from_profile(data), P.ProgramSpans.from_profile(data)


@pytest.fixture(scope="module")
def plain():
    return _traces(TPU_TRACE)


@pytest.fixture(scope="module")
def program():
    return _traces(PROGRAM_TRACE)


def _read(name, trace):
    ctx = dict(trace=trace, window=trace.window(), peaks=PEAKS,
               record=RECORD, cell=None)
    return load_module("metrics", name).read(ctx)


def test_program_spans_are_kept(plain, program):
    assert PROGRAM_TRACE.count("repro.stream.") == 7
    assert plain[1].events == []
    names = [n for n, _, _, _ in program[1].events]
    assert names.count("repro.stream.step") == 2
    assert names.count("lower_sharding_computation") == 2
    assert "PjitFunction(step)" not in names
    steps = program[1].intervals("repro.stream.step", (0, 10_000))
    assert [m for _, _, m in steps] == [
        {"step": 0, "frames": 4, "groups": 1}, {"step": 1}]
    # a compile is named by the innermost jitted call around it
    lowered = [(s, m) for n, s, _, m in program[1].events
               if n == "lower_sharding_computation"]
    assert sorted(lowered) == [(7200, {"function": "step"}),
                               (10200, {"function": "?"})]


def test_benchmark_readers_ignore_program_spans(plain, program):
    """Every per-layer reader, and the breakdown, read the same with and
    without the program's spans in the trace."""
    (tr0, _), (tr1, _) = plain, program
    w = tr0.window()
    assert tr1.window() == w and tr1.spans == tr0.spans
    for name in READERS:
        want = _read(name, tr0)
        assert want is not None, name
        assert _read(name, tr1) == want, name
    assert tr1.top_ops(w) == tr0.top_ops(w)
    assert tr1.idle_gaps(w) == tr0.idle_gaps(w)


def test_innermost_pieces():
    spans = [("a", 0, 10), ("b", 2, 6), ("c", 2, 4), ("d", 8, 12)]
    assert P.innermost(spans) == [
        (0, 2, "a"), (2, 4, "c"), (4, 6, "b"), (6, 8, "a"), (8, 10, "d"),
        (10, 12, "d")]
    assert P.overlap_each([(0, 2, "a"), (2, 4, "c"), (6, 9, "a")],
                          [(1, 3), (5, 7), (8, 20)]) == [1, 1, 2]


def test_phase_and_idle_helpers(program):
    trace, spans = program
    w = trace.window()
    assert spans.phase_per_step("repro.stream.stack", w) == [500, 200]
    assert spans.phase_per_step("repro.stream.delta", w) == [0, 400]
    assert spans.phase_per_step("repro.stream.split", w) == [200, 600]
    # idle [2, 3), [4, 6), [9, 10) us; what lies in a step goes to the
    # innermost span over it, and the wait [4.5, 6) and [9.8, 10) to none
    idle = T.gaps(trace.busy(trace.chips()[0], w), w)
    assert spans.idle_by_span(idle, w) == {
        "repro.stream.h2d": 500, "repro.stream.concat": 300,
        "repro.stream.step": 500, "repro.stream.compute": 200,
        "repro.stream.split": 800}


def test_report_on_the_described_trace(program, capsys):
    out = P.report(*program)
    assert out["steps"] == 2
    assert out["step_ms"] == pytest.approx(3.9e-3)
    ph = out["phase_ms"]
    assert ph["stack"] == pytest.approx(0.35e-3)
    assert ph["concat"] == pytest.approx(0.35e-3)
    assert ph["delta"] == pytest.approx(0.2e-3)
    assert ph["split"] == pytest.approx(0.4e-3)
    assert ph["intake"] == 0
    # idle in steps: [2, 3) + [4, 4.5) + [9, 9.8) = 2.3 us, of which
    # [2.8, 3), [4.4, 4.5) and [9.6, 9.8) lie under no phase
    assert out["unattributed_idle_pct"] == pytest.approx(100 * 0.5 / 2.3)
    assert out["idle_s_by_span"]["repro.stream.split"] == pytest.approx(8e-7)
    # the lowering after the window does not count
    assert out["compiles"] == 1 and out["compiled"] == ["step"]
    assert out["compile_s"]["backend_compile_and_load"] == pytest.approx(8e-7)
    printed = capsys.readouterr().out
    assert "device idle 0.000001 s under repro.stream.split" in printed
    assert "step 1 0.004 ms, most in repro.stream.compute 0.002 ms" in printed
    json.dumps(out)


def test_report_without_program_spans(plain):
    """A trace of a program without the spans reads no steps and no
    compiles."""
    out = P.report(*plain)
    assert out["steps"] == 0 and out["compiles"] == 0
    assert "phase_ms" not in out and "unattributed_idle_pct" not in out


def test_traced_stream_window_on_the_cpu():
    """The script's run at a tiny size: every phase of the engine's step
    ran inside the window, and nothing compiled there."""
    out = P.run(["--workload", "cam1080-noisy", "--seed", str(2**31 + 7),
                 "--seconds", "0.5"], require_tpu=False,
                overrides=tiny("cam1080-noisy"))
    assert out["steps"] > 0
    for phase in ("intake", "stack", "h2d", "concat", "compute", "split",
                  "account"):
        assert out["phase_ms"][phase] > 0, phase
    assert out["compiles"] == 0, out["compiled"]
    assert out["e2e"]["latency_p50_ms"] > 0
    json.dumps(out)
