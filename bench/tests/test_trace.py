"""The trace reducers and per-layer readers: on a small trace recorded on
the CPU (spans, no device), and on a described TPU trace whose device
intervals are known."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from bench import trace as T
from bench.spec import load_module

PEAKS = {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}

# One chip: ops at [0, 2) us (XLA), [3, 4) us (Pallas), [6, 9) us (XLA);
# harness spans: window [0, 10) us, steps [0, 4.5) and [6, 10), a wait in
# [4.5, 6). Times below are in ns from the line's timestamp.
TPU_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 3000000 }
  }
  lines {
    id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fusion.1 = f32[4,8,8]{2,1,0} fusion(f32[4,8,8]{2,1,0} %p0)" } }
  event_metadata { key: 2 value { id: 2
    name: "%edge_pallas.1 = (f32[4,8,8]{2,1,0}) custom-call(f32[4,8,8]{2,1,0} %p0)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
  event_metadata { key: 4 value { id: 4
    name: "%reduce_max.2 = f32[4]{0} reduce(f32[4,8,8]{2,1,0} %custom-call.1)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4500000 }
    events { metadata_id: 3 offset_ps: 4500000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.pace_wait" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(step)" } }
}
"""


@pytest.fixture(scope="module")
def tpu_trace():
    return T.Trace.from_profile(ProfileData.from_text_proto(TPU_TRACE))


def _ctx(trace, **record):
    return dict(trace=trace, window=trace.window(), peaks=PEAKS,
                record=record, cell=None)


def _read(name, ctx):
    return load_module("metrics", name).read(ctx)


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.overlap([(0, 3), (5, 8)], [(2, 6)]) == 2
    assert T.gaps([(1, 2), (4, 5)], (0, 6)) == [(0, 1), (2, 4), (5, 6)]
    assert T.clip([(0, 4), (6, 9)], (2, 7)) == [(2, 4), (6, 7)]


def test_device_ops_and_spans(tpu_trace):
    assert tpu_trace.chips() == ["/device:TPU:0"]
    assert tpu_trace.window() == (0, 10_000)
    w = tpu_trace.window()
    assert tpu_trace.mean_busy_s(w) == pytest.approx(6e-6)
    assert tpu_trace.mean_busy_s(w, kernel=True) == pytest.approx(1e-6)
    assert tpu_trace.mean_busy_s(w, kernel=False) == pytest.approx(5e-6)
    # a kernel is an instruction whose opcode is custom-call, not one that
    # reads a custom call's result
    assert tpu_trace.top_ops(w) == [["%reduce_max.2", 3e-6],
                                    ["%fusion.1", 2e-6],
                                    ["%edge_pallas.1", 1e-6]]
    # idle: [2, 3) in the first step, [4, 6) across the wait, [9, 10)
    gaps = tpu_trace.idle_gaps(w)
    assert gaps[0] == ["bench.pace_wait", 2e-6]
    assert sorted(g[1] for g in gaps) == pytest.approx([1e-6, 1e-6, 2e-6])


def test_readers_on_the_described_trace(tpu_trace):
    ctx = _ctx(tpu_trace, requests=2, steps=2, min_bytes=500, min_ops=10)
    # least time 500 B / 1e9 B/s = 0.5 us against 1 us of kernel
    assert _read("kernel_hbm_roofline", ctx) == pytest.approx(50.0)
    assert _read("kernel_ms.stream", ctx) == pytest.approx(0.5e-3)
    assert _read("xla_device_ms.image", ctx) == pytest.approx(2.5e-3)
    assert _read("xla_device_ms.stream", ctx) == pytest.approx(2.5e-3)
    assert _read("device_idle_share.image", ctx) == pytest.approx(40.0)
    # steps cover 8.5 us, of which the device is busy 6
    assert _read("device_idle_in_step.stream", ctx) == pytest.approx(
        100 * 2.5 / 8.5)


def test_readers_on_counters():
    ctx = dict(trace=None, window=None, peaks=None, cell=None, record=dict(
        h2d_s=[0.001, 0.003, 0.002], transfer_ms=[1.0, 5.0, 2.0],
        tiles_per_frame=10, frames_counted=4, skipped_tiles=30,
        latency_s=[0.001 * i for i in range(1, 101)]))
    # numpy's linear percentile of 1..100 ms: 95.05 ms
    assert _read("latency_p95_ms", ctx) == pytest.approx(95.05)
    assert _read("h2d_ms.image", ctx) == pytest.approx(2.0)
    assert _read("h2d_ms.stream", ctx) == pytest.approx(2.0)
    assert _read("tile_skip_share", ctx) == pytest.approx(75.0)


def test_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    tr = T.Trace.from_dir(str(tmp_path))
    w = tr.window()
    assert w is not None and w[1] > w[0]
    steps = tr.span_intervals("bench.step")
    assert len(steps) == 3 and all(w[0] <= s < e <= w[1] for s, e in steps)
    # The CPU is no TPU plane: the device readers find nothing to read,
    # and say so by returning nothing, never 0.
    assert tr.chips() == []
    ctx = _ctx(tr, requests=3, steps=3, min_bytes=1, min_ops=1)
    for name in ("kernel_hbm_roofline", "kernel_ms.stream",
                 "xla_device_ms.image", "device_idle_share.image",
                 "device_idle_in_step.stream"):
        assert _read(name, ctx) is None, name
