"""Open-loop camera streams through ``serve/streams.StreamEngine``.

``streams`` cameras at ``fps``, phase-aligned: every camera's frame ``i``
is due at ``t0 + i / fps`` on the wall clock, so each tick is one batch.
The harness makes each camera's frame for the next tick, sleeps until the
tick is due and calls ``engine.step()``; when a step runs past the next due
time the next tick starts at once, so a slow engine queues frames and their
latency, taken from the due time to the return of the step that served
them, counts the wait. The engine is configured as
``launch/serve.serve_streams`` configures it: magnitude with peaks, NMS and
hysteresis edges. Only a frame served at the first try by the configured
backend counts as served: one the engine's guard retried, or served on its
fallback, counts as failed, and the record names the backend the window
ended on.

Traffic parameters: ``streams``, ``fps``, ``motion_px``, ``noise_sigma``,
``noise_pool``, ``warm_ticks`` (ticks served before the window, unpaced,
which compile the cold step and the delta test with the masked step) and
``sample_ticks`` (ticks drawn from the seed whose frames are compared with
the reference once the window has closed).
"""
from __future__ import annotations

import random
import time

import numpy as np

SERVED = ("served", "retried", "degraded")   # the kinds that carry outputs
GRACE_S = 60.0


class Loop:
    def __init__(self, cell, seed: int):
        from repro.serve import StreamEngine, StreamRequest

        from bench import frames

        tr = cell.traffic
        self.cell, self.seed = cell, seed
        self.cfg = cell.repro_config()
        self.edge_cfg = self.cfg.edge_config(
            with_max=True, nms=True, hysteresis=True
        ).resolved()
        self.n = int(tr["streams"])
        self.fps = float(tr["fps"])
        self.h, self.w = self.cfg.image_h, self.cfg.image_w
        if cell.config["frame_dtype"] != "uint8":
            raise ValueError("open_streams serves u8 camera frames")
        self.cams = frames.CameraStreams(
            self.n, self.h, self.w, seed=seed, motion=float(tr["motion_px"]),
            sigma=float(tr["noise_sigma"]), pool=int(tr.get("noise_pool", 1)),
        )
        # One frame buffer per camera. The engine pulls frame i + 1 as it
        # finishes frame i; the buffer it holds is filled before i + 1 is due.
        self.buf = [np.zeros((self.h, self.w), np.uint8) for _ in range(self.n)]
        self.asked = [0] * self.n

        def source(sid):
            def pull(i):
                self.asked[sid] = i
                return self.buf[sid]
            return pull

        class Engine(StreamEngine):
            # Keep device references to what each served frame returned,
            # not host copies: only the sampled ones are kept past a step.
            @staticmethod
            def _host_outputs(result, b):
                return (result, b)

        self.engine = Engine(self.edge_cfg, max_streams=self.n, collect=True)
        for sid in range(self.n):
            self.engine.submit(StreamRequest(sid=sid, frames=source(sid),
                                             fps=self.fps))
        self.backend = self.engine.health.backend
        self.seen = 0
        self.warm = int(tr.get("warm_ticks", 3))
        self.record = {}
        self.kept = {}
        for _ in range(self.warm):
            self._fill()
            self.engine.step()
            self._collect(None, time.perf_counter())

    def _fill(self):
        for sid in range(self.n):
            self.cams.fill(self.buf[sid], sid, self.asked[sid])

    def _stats(self):
        return {s.stats.sid: s.stats for s in self.engine.slots if s is not None}

    def _collect(self, keep, t_done):
        """Outcomes of the step that just returned: (stream, frame, kind)
        with the time it returned; sampled outputs are kept."""
        new = self.engine.outcomes[self.seen:]
        self.seen = len(self.engine.outcomes)
        stats = self._stats()
        outs = {sid: list(st.outputs) for sid, st in stats.items()}
        for st in stats.values():
            st.outputs.clear()
        done = []
        for o in new:
            done.append((o.stream, o.frame, o.kind))
            if o.kind in SERVED:
                res = outs[o.stream].pop(0)
                if keep is not None and o.frame in keep:
                    self.kept[(o.stream, o.frame)] = res
        return [(sid, f, kind, t_done) for sid, f, kind in done]

    def measure(self, seconds: float) -> dict:
        import jax

        period = 1.0 / self.fps
        ticks = max(1, int(round(seconds * self.fps)))
        first = self.warm                      # frame index of tick 0
        rng = random.Random(self.seed)
        n_keep = min(ticks, int(self.cell.traffic.get("sample_ticks", 4)))
        keep = {first + t for t in rng.sample(range(ticks), n_keep)}
        self.keep = keep
        stats = self._stats()
        skip0 = {sid: (st.skipped_tiles, st.frames, len(st.transfer_ms))
                 for sid, st in stats.items()}
        outcomes, late = [], []
        t0 = time.perf_counter() + 0.05
        close = t0 + ticks * period
        steps = 0
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                due_idx = max(self.asked)
                tick = due_idx - first
                if tick >= ticks or time.perf_counter() > close + GRACE_S:
                    break
                due = t0 + tick * period
                # The cameras' frames for this tick are made before it is
                # due: the generator's work is not the engine's.
                with jax.profiler.TraceAnnotation("bench.generate"):
                    self._fill()
                wait = due - time.perf_counter()
                if wait > 0:
                    with jax.profiler.TraceAnnotation("bench.pace_wait"):
                        time.sleep(wait)
                late.append(time.perf_counter() - due)
                with jax.profiler.TraceAnnotation("bench.step"):
                    self.engine.step()
                t_done = time.perf_counter()
                steps += 1
                outcomes += self._collect(keep, t_done)
        t_end = time.perf_counter()
        lat, failed, served = [], 0, 0
        kinds = {}
        due_frames = {(sid, first + t) for sid in range(self.n)
                      for t in range(ticks)}
        for sid, f, kind, t_done in outcomes:
            if (sid, f) not in due_frames:
                continue
            due_frames.discard((sid, f))
            due = t0 + (f - first) * period
            kinds[kind] = kinds.get(kind, 0) + 1
            if kind == "served":
                served += 1
                lat.append(t_done - due)
            else:
                failed += 1
                lat.append(t_end - due)
        for sid, f in due_frames:          # never came
            failed += 1
            lat.append(t_end - (t0 + (f - first) * period))
        self.missing_sampled = sum(
            1 for sid in range(self.n) for f in keep
            if (sid, f) not in self.kept
        )
        stats = self._stats()
        tiles = next(iter(stats.values())).tiles_per_frame if stats else 0
        skipped = sum(st.skipped_tiles - skip0[sid][0]
                      for sid, st in stats.items())
        frames_in = sum(st.frames - skip0[sid][1] for sid, st in stats.items())
        st0 = stats[min(stats)]
        xfer = st0.transfer_ms[skip0[min(stats)][2]:]
        self.record = dict(
            attempted=ticks * self.n, failed=failed, served=served,
            window_s=t_end - t0, ticks=ticks, steps=steps,
            tiles_per_frame=tiles, skipped_tiles=skipped,
            frames_counted=frames_in, transfer_ms=xfer, lateness_s=late,
            latency_s=lat, backend=self.backend,
            backend_ran=self.engine.health.backend, kinds=kinds,
        )
        e2e = {}
        if lat:
            e2e["latency_p95_ms"] = float(np.percentile(lat, 95)) * 1e3
            e2e["latency_p50_ms"] = float(np.percentile(lat, 50)) * 1e3
        print(f"open loop: {self.n} stream(s) x {ticks} tick(s) at "
              f"{self.fps} fps of {self.h}x{self.w} u8; backend "
              f"{self.engine.health.backend}; {served} served, {failed} "
              f"failed; health: {self.engine.health.summary()}")
        if late:
            half = len(late) // 2
            print(f"generator lateness ms p50 {np.percentile(late, 50) * 1e3:.3f} "
                  f"max {max(late) * 1e3:.3f}; first half mean "
                  f"{np.mean(late[:half] or [0]) * 1e3:.3f}, second half mean "
                  f"{np.mean(late[half:]) * 1e3:.3f}")
        if lat:
            worst = int(np.argmax(late))
            print(f"frame latency ms p50 {e2e['latency_p50_ms']:.3f} p95 "
                  f"{e2e['latency_p95_ms']:.3f} max {max(lat) * 1e3:.3f} over "
                  f"{len(lat)} frame(s); latest tick start {worst} "
                  f"({late[worst] * 1e3:.3f} ms); skipped tiles {skipped} of "
                  f"{tiles * frames_in}")
        return e2e

    def release(self) -> None:
        self.engine = None

    def check(self, checks, reference) -> None:
        import jax.numpy as jnp

        checks.missing(self.missing_sampled)
        for f in sorted(self.keep):
            sids = [sid for sid in range(self.n) if (sid, f) in self.kept]
            if not sids:
                continue
            got = {"magnitude": [], "edges": []}
            for sid in sids:
                result, b = self.kept[(sid, f)]
                got["magnitude"].append(result.magnitude[b])
                got["edges"].append(result.edges[b])
            got = {k: jnp.stack(v) for k, v in got.items()}
            host = np.stack([self.cams.frame(sid, f) for sid in sids])
            want = reference.outputs(jnp.asarray(host), edges=True)
            checks.frames_pair(got, want)
        print(f"compared frames {sorted(self.keep)} of {self.n} stream(s) "
              "with the reference")


def setup(cell, seed: int) -> Loop:
    return Loop(cell, seed)
