"""Closed-loop image serving: one client, one request in flight.

The step is the one ``launch/serve.serve_image`` builds for a config:
``image_edge_config`` -> ``jax.jit(edge_detect)`` under
``serve.guard.StepGuard`` with the XLA twin as its fallback, fed by
``jax.device_put``. Each request's batch comes from a pool of distinct
seeded batches made in set-up; a request is timed from the start of its
host->device copy to its result being ready. Only a request served at the
first try by the configured backend counts as served: one the guard
retried, or served on its fallback, counts as failed, and the record names
the backend the window ended on.

Traffic parameters: ``frames_per_request``, ``distinct_requests``,
``edges`` (NMS + hysteresis edge maps instead of magnitude) and
``sample_requests`` (how many served requests are kept, drawn from the
seed, to compare with the reference once the window has closed).
"""
from __future__ import annotations

import random
import time

import numpy as np

KINDS = ("served", "retried", "degraded")


class Loop:
    def __init__(self, cell, seed: int):
        import jax

        from repro.api import edge_detect
        from repro.kernels.dispatch import resolve_backend
        from repro.launch.serve import image_edge_config
        from repro.serve.guard import GuardPolicy, StepGuard

        from bench import frames
        from bench.traffic_bytes import edge_ops, frame_bytes

        tr = cell.traffic
        self.cell, self.seed = cell, seed
        self.cfg = cell.repro_config()
        self.edges = bool(tr.get("edges", False))
        self.edge_cfg = image_edge_config(self.cfg, edges=self.edges)
        self.backend = resolve_backend(self.edge_cfg.backend)
        fb_cfg = (self.edge_cfg.replace(backend="xla")
                  if self.backend != "xla" else None)
        primary = jax.jit(lambda f: edge_detect(f, self.edge_cfg))
        fallback = (jax.jit(lambda f: edge_detect(f, fb_cfg))
                    if fb_cfg is not None else None)

        def run(step):
            def call(x):
                out = step(x)
                jax.block_until_ready(out)
                return out
            return call

        self.guard = StepGuard(run(primary),
                               fallback=run(fallback) if fallback else None,
                               policy=GuardPolicy(), seed=0)
        self.n = int(tr["frames_per_request"])
        self.h, self.w = self.cfg.image_h, self.cfg.image_w
        dtype = cell.config["frame_dtype"]
        self.pool = frames.image_requests(
            seed, int(tr["distinct_requests"]), self.n, self.h, self.w, dtype,
        )
        # the least bytes and operations of one request, for the roofline
        self.least = (frame_bytes(self.n, self.h, self.w, dtype),
                      edge_ops(self.n, self.h, self.w, cell.reference().BANK))
        self.kinds = {k: 0 for k in KINDS}
        self.retries = 0
        self.samples = []          # (request, pool index, outputs)
        self.record = {}
        for req in range(min(3, len(self.pool))):   # compile, then steady
            self._serve(self.pool[req])
        self.kinds = {k: 0 for k in KINDS}
        self.retries = 0

    def _serve(self, host):
        import jax

        with jax.profiler.TraceAnnotation("bench.h2d"):
            x = jax.device_put(host)
            x.block_until_ready()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            out, kind, attempts = self.guard(x)
        self.kinds[kind] += 1
        self.retries += attempts
        return out, kind, t1

    def measure(self, seconds: float) -> dict:
        import jax

        keep = int(self.cell.traffic.get("sample_requests", 4))
        rng = random.Random(self.seed)
        lat, h2d = [], []
        failed = 0
        t_start = time.perf_counter()
        deadline = t_start + seconds
        t_end = t_start
        req = 0
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.perf_counter() < deadline:
                idx = req % len(self.pool)
                t0 = time.perf_counter()
                try:
                    out, kind, t1 = self._serve(self.pool[idx])
                except Exception as err:  # noqa: BLE001 - the guard gave up
                    kind = f"{type(err).__name__}: {err}"
                t_end = time.perf_counter()
                if kind != "served":
                    failed += 1
                    print(f"request {req} not served at the first try: {kind}")
                    req += 1
                    continue
                lat.append(t_end - t0)
                h2d.append(t1 - t0)
                # reservoir sample of the served requests, drawn from the seed
                if len(self.samples) < keep:
                    self.samples.append((req, idx, out))
                else:
                    j = rng.randrange(req + 1)
                    if j < keep:
                        self.samples[j] = (req, idx, out)
                req += 1
        window = t_end - t_start
        done = len(lat)
        self.record = dict(
            attempted=req, failed=failed, window_s=window, requests=done,
            h2d_s=h2d, latency_s=lat,
            min_bytes=self.least[0] * done, min_ops=self.least[1] * done,
            backend=self.backend,
            backend_ran="xla" if self.guard.degraded else self.backend,
            kinds=dict(self.kinds),
        )
        e2e = {}
        if done:
            e2e["mpix_per_s"] = done * self.n * self.h * self.w / window / 1e6
        print(f"closed loop: {done} request(s) of {self.n} x {self.h}x{self.w} "
              f"{self.cell.config['frame_dtype']} in {window:.6f} s; "
              f"backend {self.backend}; kinds {self.kinds}; "
              f"retries {self.retries}; failed {failed}")
        if done:
            slow = int(np.sum(np.asarray(lat) > 2 * np.median(lat)))
            print(f"request ms p50 {np.percentile(lat, 50) * 1e3:.3f} "
                  f"p95 {np.percentile(lat, 95) * 1e3:.3f} max "
                  f"{max(lat) * 1e3:.3f} over {done}, {slow} over twice the "
                  f"median; h2d ms p50 {np.percentile(h2d, 50) * 1e3:.3f}; "
                  f"time outside requests {(window - sum(lat)) * 1e3:.3f} ms")
        return e2e

    def release(self) -> None:
        """Free what the window used, keeping the sampled outputs."""
        self.guard = None

    def check(self, checks, reference) -> None:
        import jax.numpy as jnp

        for req, idx, out in sorted(self.samples, key=lambda s: s[0]):
            got = {"magnitude": out.magnitude}
            if self.edges:
                got["edges"] = out.edges
            for f in range(self.n):
                one = {k: v[f:f + 1] for k, v in got.items()}
                want = reference.outputs(jnp.asarray(self.pool[idx][f:f + 1]),
                                         edges=self.edges)
                checks.frames_pair(one, want)
        print(f"compared requests {[s[0] for s in sorted(self.samples)]} "
              "with the reference")


def setup(cell, seed: int) -> Loop:
    return Loop(cell, seed)
