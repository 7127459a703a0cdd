"""The traffic's frames, made from the seed in set-up and never in a window.

The scene is ``repro.data.synthetic``'s (a textured sinusoid background, a
bright disk, optional Gaussian sensor noise, clipped to [0, 255]), rebuilt
so that it costs little:

  * a batch of still images is made on the device by one jitted call per
    request, from a key derived from the seed, then copied to the host;
  * a camera stream keeps its background, with and without the disk's
    brightness and with each noise field of a small pool, as u8 planes made
    once; a frame is one copy of a plane plus the disk painted into its
    bounding box. The cameras' scenes are one fixed set for a number of
    cameras, dealt to the cameras in an order drawn from the seed, so every
    seed asks the same work of a stream engine; the noise comes from the
    seed.
"""
from __future__ import annotations

import functools

import numpy as np

DISK_GAIN = 120.0


def jax_seed(seed: int, *stream: int) -> int:
    """A 32-bit key seed from any whole number and a stream of ints."""
    return int(np.random.SeedSequence([seed, *stream]).generate_state(1)[0])


@functools.lru_cache(maxsize=None)
def _image_program(frames: int, h: int, w: int, dtype: str):
    import jax
    import jax.numpy as jnp

    def one(key):
        ka, kb, kc, kr, kn = jax.random.split(key, 5)
        a, b = jax.random.uniform(ka, (2,), minval=8.0, maxval=64.0)
        cy, cx = jax.random.uniform(kc, (2,)) * jnp.array([h, w], jnp.float32)
        r = jax.random.uniform(kr, (), minval=min(h, w) / 8.0,
                               maxval=min(h, w) / 3.0)
        y = jnp.arange(h, dtype=jnp.float32)
        x = jnp.arange(w, dtype=jnp.float32)
        base = 40.0 + 50.0 * jnp.cos(y / b)[:, None] * jnp.sin(x / a)[None, :]
        disk = ((y - cy) ** 2)[:, None] + ((x - cx) ** 2)[None, :] < r * r
        img = base + DISK_GAIN * disk + 2.0 * jax.random.normal(kn, (h, w))
        return jnp.clip(img, 0.0, 255.0).astype(dtype)

    def batch(seed32, request):
        key = jax.random.fold_in(jax.random.key(seed32), request)
        return jax.vmap(one)(jax.random.split(key, frames))

    return jax.jit(batch)


def image_requests(seed: int, count: int, frames: int, h: int, w: int,
                   dtype: str) -> list:
    """``count`` distinct host batches of ``frames`` images each."""
    import jax

    prog = _image_program(frames, h, w, dtype)
    s32 = np.uint32(jax_seed(seed, 0))
    return [np.asarray(jax.device_get(prog(s32, i))) for i in range(count)]


class CameraStreams:
    """``n`` synthetic u8 cameras of ``(h, w)``, the disk moving ``motion``
    px per frame, noise of ``sigma`` grey levels cycled from a pool of
    ``pool`` fields drawn from the seed. The ``n`` scenes (background and
    disk direction) are the same for every seed; the seed deals them to
    the cameras: camera ``s`` shows scene ``order[s]``, and its frame ``i``
    takes noise field ``(i + order[s]) % pool``."""

    def __init__(self, n: int, h: int, w: int, *, seed: int, motion: float,
                 sigma: float, pool: int):
        rng = np.random.default_rng([seed, 1])
        scenes = np.random.default_rng([n, 2])
        self.h, self.w, self.motion = h, w, motion
        self.r = min(h, w) / 6.0
        y = np.arange(h, dtype=np.float32)
        x = np.arange(w, dtype=np.float32)
        self._y, self._x = y, x
        pool = max(1, int(pool)) if sigma > 0 else 1
        noise = [rng.standard_normal((h, w), dtype=np.float32) * np.float32(sigma)
                 if sigma > 0 else np.float32(0.0) for _ in range(pool)]
        params = [(scenes.uniform(8.0, 64.0, 2), scenes.uniform(0.0, 2.0 * np.pi))
                  for _ in range(n)]
        self.order = [int(k) for k in rng.permutation(n)]
        self.angle = []
        self.lo, self.hi = [], []
        for k in self.order:
            (a, b), angle = params[k]
            self.angle.append(angle)
            base = (40.0 + 50.0 * np.outer(np.cos(y / b), np.sin(x / a))
                    ).astype(np.float32)
            self.lo.append([np.clip(base + nz, 0, 255).astype(np.uint8)
                            for nz in noise])
            self.hi.append([np.clip(base + DISK_GAIN + nz, 0, 255).astype(np.uint8)
                            for nz in noise])

    def disk(self, stream: int, index: int):
        ang = self.angle[stream]
        cx = (self.w / 2.0 + self.motion * index * np.cos(ang)) % self.w
        cy = (self.h / 2.0 + self.motion * index * np.sin(ang)) % self.h
        return cy, cx

    def fill(self, out: np.ndarray, stream: int, index: int) -> np.ndarray:
        """Write frame ``index`` of ``stream`` into ``out`` (u8, (h, w))."""
        k = (index + self.order[stream]) % len(self.lo[stream])
        lo, hi = self.lo[stream][k], self.hi[stream][k]
        np.copyto(out, lo)
        cy, cx = self.disk(stream, index)
        r = self.r
        y0, y1 = max(0, int(cy - r)), min(self.h, int(cy + r) + 2)
        x0, x1 = max(0, int(cx - r)), min(self.w, int(cx + r) + 2)
        if y0 < y1 and x0 < x1:
            inside = (((self._y[y0:y1] - cy) ** 2)[:, None]
                      + ((self._x[x0:x1] - cx) ** 2)[None, :]) < r * r
            out[y0:y1, x0:x1] = np.where(inside, hi[y0:y1, x0:x1],
                                         lo[y0:y1, x0:x1])
        return out

    def frame(self, stream: int, index: int) -> np.ndarray:
        return self.fill(np.empty((self.h, self.w), np.uint8), stream, index)
