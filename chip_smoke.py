#!/usr/bin/env python3
"""Smoke run of the edge engine on a TPU: does the main path start, compile
and agree bit for bit with the XLA reference at full size?

    python chip_smoke.py              # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4    # four chips: the sharded halo path

One chip drives the serving entry points (``repro.launch.serve``) at the
full ``sobel-hd`` configuration — 2048x2048 frames, ``sobel5``, 4
directions, RG-v2, 64x256 blocks — with ``backend="pallas-tpu"``:

  (a) image serving: 4 frames per request, magnitude with per-image peak;
  (b) the same with ``--edges``: fused NMS plus hysteresis;
  (c) the stream engine with 2 uint8 camera streams through the masked
      delta-skip kernel (``edge_stream_pallas``).

Each phase's outputs are compared with ``np.array_equal`` against
``backend="xla"`` on the same chip and the same frames. ``--chips 4`` runs
only phase (a) under a 1x2x2 image mesh (halo exchange between the four
chips) and compares it with the same frames on one chip of that host.

Times printed here are smoke timings, not benchmark metrics. Any failure —
no TPU, a phase served by another backend, a single guard retry or
degrade, an output that differs — exits non-zero before the last line,
which on success is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ARCH = "sobel-hd"
PLATFORM = "tpu"
KERNEL_BACKEND = "pallas-tpu"
REFERENCE_BACKEND = "xla"
REQUESTS = 3          # per image phase, after the warm-up request
FRAMES_PER_REQUEST = 4
STREAMS = 2
STREAM_FRAMES = 4     # frames 0 and 1 compile (cold step; delta + masked step)

_HERE = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(RuntimeError):
    """A phase did not run as the contract requires."""


def _import_repro():
    """Import the package from this checkout (and only from it)."""
    src = os.path.join(_HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no repro package under {src}; run from a checkout")
    sys.path.insert(0, src)
    import repro

    if not os.path.abspath(repro.__file__).startswith(src + os.sep):
        raise SmokeFailure(f"repro imported from {repro.__file__}, not {src}")


def _timing(label, compile_s, steady_ms):
    print(f"  {label} smoke timing (not a metric): first call incl. compile "
          f"{compile_s:.3f} s; steady median {np.median(steady_ms):.3f} ms "
          f"over {len(steady_ms)} call(s)")


def _compare(name, got, want):
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        raise SmokeFailure(f"{name}: {got.dtype}{got.shape} vs reference "
                           f"{want.dtype}{want.shape}")
    if not np.array_equal(got, want):
        bad = got != want
        diff = np.abs(got.astype(np.float64) - want.astype(np.float64))
        raise SmokeFailure(
            f"{name}: {int(bad.sum())} of {bad.size} values differ from "
            f"{REFERENCE_BACKEND} (max |diff| {float(diff.max()):.6g})"
        )


def _check_guard(phase, health, requests):
    """The phase was served by the kernel backend, first try, every time."""
    c = health.counts
    print(f"  {phase}: backend={health.backend} served={c['served']} "
          f"retried={c['retried']} degraded={c['degraded']} "
          f"retries={health.retries}")
    if health.backend != KERNEL_BACKEND:
        raise SmokeFailure(f"{phase} served by {health.backend}, "
                           f"not {KERNEL_BACKEND}")
    if health.retries or health.degraded or c["retried"] or c["degraded"]:
        raise SmokeFailure(f"{phase}: the serving guard retried or degraded")
    if c["served"] != requests:
        raise SmokeFailure(f"{phase}: {c['served']} of {requests} served")


def _serve_args(*extra):
    from repro.launch import serve

    return serve.build_parser().parse_args(["--arch", ARCH] + list(extra))


def _config(backend):
    """The arch's full config, as ``serve.main`` loads it, on ``backend``."""
    from repro.configs import get_config

    return get_config(ARCH).replace(dtype="float32", sobel_backend=backend)


def _block_source(edge_cfg, cfg):
    from repro.kernels import dispatch

    bh, bw, source = dispatch.choose_block_shape(
        cfg.image_h, cfg.image_w, block_h=edge_cfg.block_h,
        block_w=edge_cfg.block_w, backend=KERNEL_BACKEND,
    )
    print(f"  block source: {source} ({bh}x{bw})")
    if source != "explicit":
        raise SmokeFailure(f"block shape came from the {source} source; "
                           f"{cfg.name} pins it")


def phase_image(edges):
    """(a) / (b): ``serve_image`` as the CLI runs it, vs the XLA step."""
    import jax

    from repro.api import edge_detect
    from repro.data.synthetic import image_batch
    from repro.kernels.dispatch import resolve_backend
    from repro.launch import serve

    name = "(b) image --edges" if edges else "(a) image"
    args = _serve_args(*(["--edges"] if edges else []),
                       "--requests", str(REQUESTS),
                       "--slots", str(FRAMES_PER_REQUEST))
    cfg = _config(KERNEL_BACKEND)
    edge_cfg = serve.image_edge_config(cfg, edges=edges)
    if resolve_backend(edge_cfg.backend) != KERNEL_BACKEND:
        raise SmokeFailure(f"{name} resolves to "
                           f"{resolve_backend(edge_cfg.backend)}")
    _block_source(edge_cfg, cfg)

    outs = {}
    report = serve.serve_image(cfg, args, on_result=lambda req, out:
                               outs.__setitem__(req, jax.device_get(out)))
    _check_guard(name, report["health"], REQUESTS)

    ref_cfg = serve.image_edge_config(
        _config(REFERENCE_BACKEND), edges=edges
    )
    ref_step = jax.jit(lambda f: edge_detect(f, ref_cfg))
    for req, out in sorted(outs.items()):
        frames = image_batch(cfg, batch=FRAMES_PER_REQUEST, step=req)["images"]
        ref = jax.device_get(ref_step(frames))
        _compare(f"{name} request {req} magnitude", out.magnitude,
                 ref.magnitude)
        _compare(f"{name} request {req} peak", out.peak, ref.peak)
        if edges:
            _compare(f"{name} request {req} edges", out.edges, ref.edges)
    if edges:
        print(f"  {name} edge density "
              f"{float(np.mean(outs[max(outs)].edges)):.4f}")
    print(f"  {name}: {len(outs)} request(s) x {FRAMES_PER_REQUEST} frames "
          f"equal to {REFERENCE_BACKEND}")
    _timing(name, report["compile_s"], report["lat_ms"])


def phase_streams():
    """(c): the stream engine over u8 camera streams, vs stateless XLA."""
    import jax

    from repro.api import edge_detect
    from repro.data.synthetic import video_frame
    from repro.launch import serve

    name = "(c) streams"
    args = _serve_args("--streams", str(STREAMS), "--slots", str(STREAMS),
                       "--requests", str(STREAM_FRAMES))
    cfg = _config(KERNEL_BACKEND)
    engine, stats = serve.serve_streams(cfg, args, collect=True)
    if engine.config.backend != KERNEL_BACKEND:
        raise SmokeFailure(f"{name} configured for {engine.config.backend}")
    _check_guard(name, engine.health, STREAMS * STREAM_FRAMES)

    ref_cfg = engine.config.replace(backend=REFERENCE_BACKEND)
    ref_step = jax.jit(lambda f: edge_detect(f, ref_cfg))
    for sid, st in sorted(stats.items()):
        if st.cached_steps >= st.frames:
            raise SmokeFailure(f"{name}: stream {sid} never ran the kernel")
        for i, out in enumerate(st.outputs):
            frame = video_frame(cfg, stream=sid, step=i, motion=args.motion)
            if frame.dtype != np.uint8:
                raise SmokeFailure(f"{name}: frames are {frame.dtype}")
            ref = jax.device_get(ref_step(frame))
            _compare(f"{name} stream {sid} frame {i} magnitude",
                     out["magnitude"], ref.magnitude)
            _compare(f"{name} stream {sid} frame {i} edges", out["edges"],
                     ref.edges)
        print(f"  {name}: stream {sid} {st.frames} frames equal to "
              f"{REFERENCE_BACKEND}, skip rate {st.skip_rate:.3f}")
    # both streams ride one batched step, so one stream's times are the
    # group's
    st = stats[min(stats)]
    _timing(name, st.compute_ms[0] / 1e3, st.compute_ms[2:])


def phase_sharded():
    """--chips 4: phase (a) on a 1x2x2 image mesh vs one chip."""
    import jax

    from repro.api import edge_detect
    from repro.data.synthetic import image_batch
    from repro.launch import serve

    name = "(a) image --shard 1x2x2"
    args = _serve_args("--shard", "1x2x2", "--requests", str(REQUESTS),
                       "--slots", str(FRAMES_PER_REQUEST))
    cfg = _config(KERNEL_BACKEND)
    edge_cfg = serve.image_edge_config(cfg)
    _block_source(edge_cfg, cfg)
    outs = {}
    report = serve.serve_image(cfg, args, on_result=lambda req, out:
                               outs.__setitem__(req, jax.device_get(out)))
    _check_guard(name, report["health"], REQUESTS)

    one = jax.devices()[0]
    one_step = jax.jit(lambda f: edge_detect(f, edge_cfg))
    for req, out in sorted(outs.items()):
        frames = image_batch(cfg, batch=FRAMES_PER_REQUEST, step=req)["images"]
        ref = jax.device_get(one_step(jax.device_put(frames, one)))
        _compare(f"{name} request {req} magnitude", out.magnitude,
                 ref.magnitude)
        _compare(f"{name} request {req} peak", out.peak, ref.peak)
    print(f"  {name}: {len(outs)} request(s) x {FRAMES_PER_REQUEST} frames "
          f"equal to the same frames on one chip ({one.device_kind})")
    _timing(name, report["compile_s"], report["lat_ms"])


def run(chips):
    """Every phase for ``chips``; returns the device record of the last
    line. Raises :class:`SmokeFailure` on any breach of the contract."""
    _import_repro()
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:  # a platform was requested and is absent
        raise SmokeFailure(f"JAX found no usable device: {err}") from err
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    print(f"device: platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devices)}; compilation cache {cache}")
    if d0.platform != PLATFORM:
        raise SmokeFailure(f"no {PLATFORM}: JAX runs on {d0.platform}")
    if len(devices) < chips:
        raise SmokeFailure(f"--chips {chips} needs {chips} devices, have "
                           f"{len(devices)}")
    t0 = time.perf_counter()
    if chips == 1:
        phase_image(False)
        phase_image(True)
        phase_streams()
    else:
        phase_sharded()
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s "
          "(smoke timing, not a metric)")
    return device


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(c) on one chip; 4: the sharded "
                         "halo path on a 2x2 mesh and its one-chip control")
    args = ap.parse_args()
    try:
        device = run(args.chips)
    except SmokeFailure as err:
        print(f"chip_smoke: FAIL: {err}", file=sys.stderr)
        sys.exit(1)
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
