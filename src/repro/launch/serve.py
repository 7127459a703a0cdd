"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--smoke]``.

LM architectures: continuous-batching engine over randomly generated prompt
traffic; reports token throughput and per-request latency percentiles.

Image architectures (``sobel-hd``): frame-serving loop over synthetic camera
traffic through the ``repro.api`` facade — the arch's ``EdgeConfig``
(operator / directions / variant / backend / block overrides) is threaded
verbatim into :func:`repro.api.edge_detect`; reports megapixels/second and
per-batch latency percentiles (the paper's Table 2 metric). ``--edges``
switches the traffic to Canny-grade binary edge maps — fused NMS in the
kernel pass plus post-gather hysteresis linking — and reports the edge
density of the final batch alongside the latency numbers.

Streaming video: ``--streams N --fps F`` switches image archs to the
continuous-batching stream engine (``repro.serve.streams``) — N synthetic
camera streams with per-stream temporal state and delta-skip tiles;
``--decay`` enables temporal hysteresis seeding. Reports per-stream p50/p99
with host→device transfer and engine compute timed separately.

Multi-device serving: ``--shard DxRxC`` (or the arch's ``sobel_shard``)
spreads every request over the image mesh — D-way batch parallelism plus an
RxC spatial grid with halo exchange (``repro.sharding.halo``). The loop is
elastic: any device-loss event replans the mesh via
``runtime.elastic.plan_image_mesh`` (the spatial grid survives, the data
axis shrinks), re-jits, and keeps serving. ``--simulate-loss-at N`` is
retained as sugar for the chaos plan entry ``loss@N``.

Fault drills: ``--chaos PLAN`` threads a deterministic
``repro.runtime.chaos.FaultPlan`` through the loop (DSL in that module's
docstring) — injected step failures walk the ``serve/guard.py`` ladder
(bounded retry → permanent bit-exact pallas→xla fallback), device-loss
events trigger elastic replans, per-device/per-stream stragglers are
detected by ``StepMonitor`` and excluded by ``StragglerPolicy``, corrupted
stream frames are quarantined, and overloaded streams shed. Every mode
prints a ``health:`` line accounting 100% of submitted work (served /
retried / degraded / shed / quarantined); under ``--chaos`` an unaccounted
frame is a hard error (non-zero exit) — the CI chaos lane's invariant.

Latency methodology: compile iterations (the initial warm-up and the
re-warm after a reshard) are excluded from the percentile window, and every
stamped request is ``block_until_ready`` on the *full* result pytree, so
p50/p95 reflect steady-state serving.

``main`` keeps JAX's persistent compilation cache where
``repro.launch.compile_cache`` says.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs), q))


def _parse_chaos(args):
    """The merged FaultPlan for this run (``--chaos`` + legacy sugar)."""
    from repro.runtime.chaos import DeviceLoss, FaultPlan

    plan = FaultPlan.parse(args.chaos) if args.chaos else None
    if args.simulate_loss_at:
        # Legacy flag == the special case ``loss@N`` (drop half, keep >= 1).
        base = plan or FaultPlan()
        plan = FaultPlan(
            base.faults + (DeviceLoss(step=args.simulate_loss_at),),
            seed=base.seed,
        )
    return plan


def image_edge_config(cfg, *, edges: bool = False):
    """The resolved ``EdgeConfig`` image serving runs for ``cfg``:
    magnitude with per-image peaks, or (``edges``) binary edge maps —
    fused NMS in the kernel pass, hysteresis linking post-gather."""
    overrides = dict(with_max=True)
    if edges:
        overrides.update(nms=True, hysteresis=True)
    return cfg.edge_config(**overrides).resolved()


def serve_image(cfg, args, on_result=None) -> dict:
    """Edge-detection serving: one request = one batch of frames.

    Each request runs under the degradation ladder (``serve/guard.py``):
    retries with backoff, then a permanent bit-exact xla fallback. A
    ``--chaos`` plan can shrink the device population mid-run (elastic
    mesh replan + re-jit, generalizing ``--simulate-loss-at``) and
    straggle individual devices (``slow@dK:MS``) — straggling devices are
    flagged by ``StepMonitor`` and, after repeated strikes, excluded from
    the mesh entirely (another replan), so the fleet heals itself.

    ``on_result(req, out)`` sees every served request's result. Returns
    the run's record: ``health``, ``compile_s`` (the first warm-up),
    ``lat_ms``/``xfer_ms`` per request and ``wall`` seconds.
    """
    import jax.numpy as jnp

    from repro.api import ShardConfig, edge_detect
    from repro.data.synthetic import image_batch
    from repro.kernels.dispatch import resolve_backend
    from repro.runtime.elastic import make_image_mesh, plan_image_mesh, reshard
    from repro.runtime.monitor import StepMonitor
    from repro.runtime.stragglers import StragglerPolicy
    from repro.serve.guard import GuardPolicy, Health, StepGuard
    from repro.sharding.partition import layout_logical_axes

    chaos = _parse_chaos(args)
    edge_cfg = image_edge_config(cfg, edges=args.edges)
    backend = resolve_backend(edge_cfg.backend)
    fb_cfg = edge_cfg.replace(backend="xla") if backend != "xla" else None
    shard_spec = args.shard if args.shard is not None else cfg.sobel_shard
    shard = ShardConfig.parse(shard_spec) if shard_spec else None
    all_devices = list(jax.devices())
    pop = list(range(len(all_devices)))  # surviving device ids, d<i> tags
    if shard is not None:
        # Strict at startup: a spec that does not fit the machine is a
        # config error, not something to silently downgrade. The clamping
        # path below is reserved for elastic *loss* of devices mid-run.
        shard.resolve(len(pop))
    print(
        f"serving {cfg.name}: operator={edge_cfg.operator} "
        f"variant={edge_cfg.variant} directions={edge_cfg.directions} "
        f"backend={edge_cfg.backend} {cfg.image_h}x{cfg.image_w} "
        f"devices={len(pop)} shard={shard_spec or 'none'}"
        f"{' mode=edges (NMS+hysteresis)' if args.edges else ''}"
        f"{f' chaos={args.chaos!r}' if args.chaos else ''}"
    )

    health = Health(backend=backend)
    monitor = StepMonitor(window=8)
    straggler_policy = StragglerPolicy()
    fns = {}  # current jitted steps; guard closures read through this

    def build_step(devs):
        """(Re)build mesh + jitted steps for the current device population."""
        if shard is None:
            mesh = None
        else:
            (d, r, c), _ = plan_image_mesh(
                len(devs), rows=shard.rows, cols=shard.cols, data=shard.data
            )
            mesh = make_image_mesh(devs, rows=r, cols=c, data=d)
            print(f"image mesh: data={d} row={r} col={c} on {d * r * c} device(s)")
        fns["primary"] = jax.jit(
            lambda frames: edge_detect(frames, edge_cfg, mesh=mesh)
        )
        if fb_cfg is not None:
            fns["fallback"] = jax.jit(
                lambda frames: edge_detect(frames, fb_cfg, mesh=mesh)
            )
        return mesh

    def _run(which, frames):
        out = fns[which](frames)
        jax.block_until_ready(out)
        return out

    guard = StepGuard(
        lambda frames: _run("primary", frames),
        fallback=(lambda frames: _run("fallback", frames))
        if fb_cfg is not None else None,
        policy=GuardPolicy(),
        chaos=chaos,
        seed=chaos.seed if chaos is not None else 0,
    )

    def place(frames, mesh):
        if mesh is None:
            return frames
        layout = "NHW" if frames.ndim == 3 else "NHWC"
        return reshard(frames, layout_logical_axes(layout), mesh, frames,
                       rules="image")

    def warm(mesh, req):
        """Pay compile outside the latency window (ladder applies here too:
        a persistent kernel failure degrades during warm-up, not mid-SLA)."""
        frames = jnp.asarray(image_batch(cfg, batch=args.slots, step=req)["images"])
        guard(place(frames, mesh))

    def replan(keep, why):
        nonlocal mesh, pop
        survivors = pop[:keep]
        print(f"{why}: {len(pop)} -> {len(survivors)} devices; "
              "replanning mesh and resharding")
        pop = survivors
        mesh = build_step([all_devices[i] for i in pop])
        health.replans += 1
        return mesh

    mesh = build_step([all_devices[i] for i in pop])
    t_warm = time.perf_counter()
    warm(mesh, req=0)
    compile_s = time.perf_counter() - t_warm

    lat_ms = []
    xfer_ms = []
    px_total = 0
    excluded = set()
    t_all = time.perf_counter()
    for req in range(args.requests):
        if chaos is not None:
            loss = chaos.device_loss(req)
            if loss is not None:
                replan(loss.survivors(len(pop)), "device loss")
                warm(mesh, req=req)  # recompile excluded from the window
        host = image_batch(cfg, batch=args.slots, step=req)["images"]
        # Transfer and compute are timed separately: the device placement is
        # block_until_ready'd on its own, so the compute percentiles measure
        # the kernel, not the host->device copy it used to silently absorb.
        t_x = time.perf_counter()
        frames = place(jnp.asarray(host), mesh)
        jax.block_until_ready(frames)
        xfer_ms.append((time.perf_counter() - t_x) * 1e3)
        t0 = time.perf_counter()
        health.submitted += 1
        out, kind, attempts = guard(frames)
        base_s = time.perf_counter() - t0
        health.record(kind)
        health.retries += attempts
        health.degraded = guard.degraded
        if guard.degraded and fb_cfg is not None:
            health.backend = "xla"
        # Injected device stragglers: the slowest device gates the batch
        # (one wall-clock sleep), but the monitor sees each device's own
        # time so detection blames the right one.
        lag = 0.0
        if chaos is not None:
            delays = [chaos.delay_s(f"d{i}", req) for i in pop]
            lag = max(delays)
            if lag > 0:
                time.sleep(lag)
            for i, own in zip(pop, delays):
                monitor.record(f"d{i}", base_s + own)
            for h in monitor.stragglers():
                if h not in health.stragglers:
                    health.stragglers.append(h)
            for host_tag in straggler_policy.step(monitor)["exclude"]:
                if host_tag in excluded or len(pop) <= 1:
                    continue
                excluded.add(host_tag)
                health.excluded.append(host_tag)
                pop = [i for i in pop if f"d{i}" != host_tag]
                replan(len(pop), f"excluding straggler {host_tag}")
                warm(mesh, req=req)
        lat_ms.append(base_s * 1e3 + lag * 1e3)
        px_total += frames.shape[0] * cfg.image_h * cfg.image_w
        if on_result is not None:
            on_result(req, out)
    wall = time.perf_counter() - t_all
    report = dict(health=health, compile_s=compile_s, lat_ms=lat_ms,
                  xfer_ms=xfer_ms, wall=wall)
    if not lat_ms:  # --requests 0: nothing but the warm-up ran
        print(f"0 requests served in {wall:.2f}s (warm-up only; "
              "use --requests >= 1 for steady-state numbers)")
        return report
    mps = px_total / 1e6 / (sum(lat_ms) / 1e3)
    tag = " (served through reshard)" if health.replans else ""
    if args.edges:
        # Observability for detector traffic: the edge-pixel density of the
        # last batch (a blank-camera or threshold misconfiguration shows up
        # here as 0.0 / ~1.0).
        tag += f"; edge density={float(jnp.mean(out.edges)):.3f}"
    print(
        f"{args.requests} requests x {args.slots} frames, {wall:.2f}s -> "
        f"{mps:.1f} MPS; compute p50={_percentile(lat_ms, 50):.1f}ms "
        f"p95={_percentile(lat_ms, 95):.1f}ms; transfer "
        f"p50={_percentile(xfer_ms, 50):.1f}ms "
        f"p95={_percentile(xfer_ms, 95):.1f}ms{tag}"
    )
    print(health.summary())
    if chaos is not None and health.unaccounted:
        raise SystemExit(
            f"chaos run left {health.unaccounted} request(s) unaccounted"
        )
    return report


def serve_streams(cfg, args, *, collect: bool = False):
    """Streaming video serving: N concurrent camera streams, fps-paced.

    Each stream is a synthetic camera (``data.synthetic.video_frame``)
    pushing ``--requests`` frames at ``--fps``; the
    :class:`~repro.serve.StreamEngine` batches same-resolution streams,
    delta-skips unchanged tiles against each stream's cached state, and
    (with ``--decay > 0``) carries temporal hysteresis seeds across frames.
    Reports per-stream p50/p99 with transfer and compute split, plus the
    delta-skip rate and fully-cached step count. Under ``--chaos`` every
    fault kind applies (stream stragglers are ``slow@s<sid>:MS``, frame
    corruption ``corrupt@<sid>:<frame>``); the run ends with the engine's
    health ledger and fails hard if any submitted frame went unaccounted.
    ``collect`` keeps each stream's outputs on its stats record. Returns
    ``(engine, stats)``.
    """
    from repro.data.synthetic import video_frame
    from repro.serve import StreamEngine, StreamRequest

    chaos = _parse_chaos(args)
    overrides = dict(with_max=True, nms=True, hysteresis=True)
    if args.decay > 0:
        overrides.update(temporal=True, decay=args.decay)
    edge_cfg = cfg.edge_config(**overrides).resolved()
    print(
        f"streaming {cfg.name}: operator={edge_cfg.operator} "
        f"variant={edge_cfg.variant} backend={edge_cfg.backend} "
        f"{cfg.image_h}x{cfg.image_w} streams={args.streams} "
        f"slots={args.slots} fps={args.fps} frames/stream={args.requests} "
        f"motion={args.motion}"
        f"{f' temporal decay={args.decay}' if args.decay > 0 else ''}"
        f"{f' chaos={args.chaos!r}' if args.chaos else ''}"
    )

    def source(sid):
        def frame(i):
            if i >= args.requests:
                return None
            return video_frame(cfg, stream=sid, step=i, motion=args.motion)
        return frame

    engine = StreamEngine(edge_cfg, max_streams=args.slots, chaos=chaos,
                          collect=collect)
    for sid in range(args.streams):
        engine.submit(StreamRequest(sid=sid, frames=source(sid), fps=args.fps))
    t0 = time.perf_counter()
    stats = engine.run()
    wall = time.perf_counter() - t0

    frames_total = 0
    for sid in sorted(stats):
        st = stats[sid]
        frames_total += st.frames
        # The first couple of samples per stream pay jit compile (cold state
        # group, then the masked/cached specialization); exclude them from
        # the steady-state percentiles, same policy as serve_image's warm().
        warm = min(2, max(0, st.frames - 1))
        comp = st.compute_ms[warm:] or st.compute_ms
        xfer = st.transfer_ms[warm:] or st.transfer_ms
        drops = (f" shed={st.shed} quarantined={st.quarantined}"
                 if st.shed or st.quarantined else "")
        print(
            f"  stream {sid}: {st.frames} frames, skip={st.skip_rate:.0%} "
            f"cached={st.cached_steps};{drops} compute "
            f"p50={_percentile(comp, 50):.2f}ms p99={_percentile(comp, 99):.2f}ms; "
            f"transfer p50={_percentile(xfer, 50):.2f}ms "
            f"p99={_percentile(xfer, 99):.2f}ms "
            f"(budget {st.budget_ms:.1f}ms)"
        )
    fps_served = frames_total / wall if wall > 0 else 0.0
    print(f"{len(stats)} streams x {args.requests} frames in {wall:.2f}s "
          f"-> {fps_served:.1f} frames/s aggregate")
    print(engine.health.summary())
    if chaos is not None and engine.health.unaccounted:
        raise SystemExit(
            f"chaos run left {engine.health.unaccounted} frame(s) unaccounted"
        )
    return engine, stats


def serve_lm(cfg, args) -> None:
    from repro.models import Model
    from repro.serve import Engine, Request

    model = Model(cfg)
    params = model.init(jax.random.key(0))
    print(f"serving {cfg.name}: {model.param_count():,} params, {args.slots} slots")

    engine = Engine(cfg, params, max_batch=args.slots, max_len=args.max_len,
                    prompt_buckets=(8, 16, 32, 64))
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for uid in range(args.requests):
        plen = int(rng.integers(2, 24))
        engine.submit(Request(uid=uid, prompt=rng.integers(0, cfg.vocab_size, plen).tolist(),
                              max_new_tokens=args.max_new))
    done = engine.run()
    dt = time.perf_counter() - t0
    toks = sum(len(r.output) for r in done)
    print(f"{len(done)} requests, {toks} tokens, {dt:.2f}s -> {toks/dt:.1f} tok/s")


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI (``python -m repro.launch.serve --help``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--streams", type=int, default=0, metavar="N",
                    help="image archs: serve N concurrent video streams "
                         "through the streaming engine (per-stream temporal "
                         "state + delta-skip); --requests = frames per stream")
    ap.add_argument("--fps", type=float, default=30.0,
                    help="per-stream frame rate budget (with --streams)")
    ap.add_argument("--decay", type=float, default=0.0,
                    help="temporal hysteresis seed decay in [0,1); 0 = "
                         "stateless per-frame detection (with --streams)")
    ap.add_argument("--motion", type=float, default=2.0,
                    help="synthetic camera motion in px/frame; 0 = static "
                         "streams, the delta-skip best case (with --streams)")
    ap.add_argument("--edges", action="store_true",
                    help="image archs: serve binary edge maps (fused NMS + "
                         "hysteresis) instead of magnitude")
    ap.add_argument("--shard", default=None,
                    help="image mesh 'DxRxC' (data x row x col) or 'auto'; "
                         "default: the arch's sobel_shard")
    ap.add_argument("--simulate-loss-at", type=int, default=0, metavar="N",
                    help="before request N, drop half the devices and "
                         "reshard (sugar for the chaos plan entry 'loss@N')")
    ap.add_argument("--chaos", default=None, metavar="PLAN",
                    help="deterministic fault-injection plan (DSL in "
                         "repro/runtime/chaos.py), e.g. "
                         "'loss@4;fail@step:1x2;slow@s1:40;corrupt@0:3=nan'; "
                         "the run prints a health ledger and exits non-zero "
                         "if any submitted frame goes unaccounted")
    return ap


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke).replace(dtype="float32")
    if cfg.family == "image":
        if args.streams > 0:
            serve_streams(cfg, args)
        else:
            serve_image(cfg, args)
        return
    for flag, on in (("--edges", args.edges), ("--shard", args.shard),
                     ("--streams", args.streams), ("--chaos", args.chaos)):
        if on:
            raise SystemExit(
                f"{flag} applies to image (detector) serving; arch "
                f"{cfg.name!r} is family {cfg.family!r}"
            )
    if cfg.family in ("encdec", "vlm"):
        raise SystemExit(f"{cfg.family} serving needs frontend inputs; use examples/")
    serve_lm(cfg, args)


if __name__ == "__main__":
    main()
