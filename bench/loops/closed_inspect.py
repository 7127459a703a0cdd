"""Closed-loop inspection serving: ``closed_batch`` with high-bit-depth
frames.

Everything but the frame pool is ``closed_batch.Loop``'s: the step
``launch/serve.image_edge_config`` builds, the guard, the timing, the
sampling and the comparison with the reference. The pool holds
``distinct_requests`` seeded batches of ``frame_bits``-bit frames (12-bit
Mono12 in uint16 containers for ``inspect-12mp``), made on the device in
set-up by one jitted call per request and copied to the host, so that each
request in the window is timed from its host->device copy as in
``closed_batch``.

A frame spans the sensor's whole range, so that an accumulator too narrow
for it cannot hide: a part on a textured background (DN 400..3200 at 12
bits), a specular highlight saturated at the largest value, a shadow at 0,
and Gaussian sensor noise of ``noise_dn`` on every pixel. It is not the
8-bit scene of ``bench/frames.py`` cast to a wider dtype, which would stay
below 256.

Traffic parameters: ``closed_batch``'s, and ``noise_dn``.
"""
from __future__ import annotations

import copy
import functools

import numpy as np

from bench import frames
from bench.loops import closed_batch

POOL_STREAM = 12     # the pool's key stream, apart from bench/frames.py's


@functools.lru_cache(maxsize=None)
def _program(n: int, h: int, w: int, full: int, noise: float, dtype: str):
    import jax
    import jax.numpy as jnp

    s = full / 4095.0      # the scene's levels are given in 12-bit DN

    def one(key):
        ka, kc, kr, ks, kn = jax.random.split(key, 5)
        a, b = jax.random.uniform(ka, (2,), minval=8.0, maxval=64.0)
        y = jnp.arange(h, dtype=jnp.float32)[:, None]
        x = jnp.arange(w, dtype=jnp.float32)[None, :]
        img = s * (1800.0 + 1400.0 * jnp.cos(y / b) * jnp.sin(x / a))
        cy, cx = jax.random.uniform(kc, (2,)) * jnp.array([h, w], jnp.float32)
        r = jax.random.uniform(kr, (), minval=min(h, w) / 8.0,
                               maxval=min(h, w) / 4.0)
        d2 = (y - cy) ** 2 + (x - cx) ** 2
        img = jnp.where(d2 < r * r, img + s * 1500.0, img)         # the part
        img = jnp.where(d2 < (r / 3.0) ** 2, 2.0 * full, img)      # glint
        sy, sx = jax.random.uniform(ks, (2,)) * jnp.array(
            [0.8 * h, 0.8 * w], jnp.float32)
        shadow = ((y >= sy) & (y < sy + h / 5.0)
                  & (x >= sx) & (x < sx + w / 5.0))
        img = jnp.where(shadow, -s * 256.0, img)
        img = img + noise * jax.random.normal(kn, (h, w))
        return jnp.clip(jnp.round(img), 0.0, float(full)).astype(dtype)

    def batch(seed32, request):
        key = jax.random.fold_in(jax.random.key(seed32), request)
        return jax.vmap(one)(jax.random.split(key, n))

    return jax.jit(batch)


def requests(seed: int, count: int, n: int, h: int, w: int, bits: int,
             noise: float, dtype: str) -> list:
    """``count`` distinct host batches of ``n`` ``bits``-bit frames."""
    import jax

    prog = _program(n, h, w, (1 << bits) - 1, float(noise), dtype)
    s32 = np.uint32(frames.jax_seed(seed, POOL_STREAM))
    return [np.asarray(jax.device_get(prog(s32, i))) for i in range(count)]


class Loop(closed_batch.Loop):
    def __init__(self, cell, seed: int):
        # closed_batch with an empty pool: it builds the step and the
        # guard, and warms nothing; the pool and the warm-up follow here.
        bare = copy.copy(cell)
        bare.traffic = {**cell.traffic, "distinct_requests": 0}
        super().__init__(bare, seed)
        self.cell = cell
        tr = cell.traffic
        bits = int(self.cfg.frame_bits)
        dtype = cell.config["frame_dtype"]
        if not bits or np.iinfo(dtype).bits < bits:
            raise ValueError(f"{bits}-bit frames do not fit {dtype}")
        self.pool = requests(seed, int(tr["distinct_requests"]), self.n,
                             self.h, self.w, bits, float(tr["noise_dn"]),
                             dtype)
        for req in range(min(3, len(self.pool))):   # compile, then steady
            self._serve(self.pool[req])
        self.kinds = {k: 0 for k in closed_batch.KINDS}
        self.retries = 0


def setup(cell, seed: int) -> Loop:
    return Loop(cell, seed)
