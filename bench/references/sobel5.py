"""Plain reference for the sobel5 configurations, written from the paper.

The four-directional 5x5 operator of arXiv:2305.00515, Eq. 3 (OpenCV's
weights, a=1, b=2, m=6, n=4), applied as four dense correlations,
``G[y, x] = sum_ij K[i, j] * I[y + i - 2, x + j - 2]``, over the image
mirrored at its border (reflect, no repeated edge pixel). From there:

  * magnitude ``sqrt(Gx^2 + Gy^2 + Gd^2 + Gdt^2)``, summed in that order,
    each square rounded on its own; the per-image peak is its maximum, and
    the served magnitude is ``magnitude * (255 / max(peak, 1e-8))``;
  * NMS: a pixel keeps its magnitude when it is ``>=`` both neighbours
    along the direction whose ``|G|`` is largest (the first of x, y, d, dt
    on ties); x looks west/east, y north/south, d along the main diagonal,
    dt along the anti-diagonal. The magnitude beyond the border is that of
    the mirrored image, so the operator runs one pixel past it;
  * hysteresis: pixels above ``high * peak`` are edges, and edges grow
    through 8-connected pixels above ``low * peak`` until nothing changes.

``dtype`` is the arithmetic of the correlations and the magnitude:
float32 is the reference; bfloat16 is the control, the next precision down.
NMS, thresholds and normalisation then run in float32 on its magnitude.
Nothing here comes from the program under test.
"""
from __future__ import annotations

import functools

import numpy as np

LOW, HIGH = 0.10, 0.20

KX = np.outer([1, 4, 6, 4, 1], [-1, -2, 0, 2, 1])
KY = KX.T
KD = np.array([[-6, -4, -1, -2, 0],
               [-4, -12, -8, 0, 2],
               [-1, -8, 0, 8, 1],
               [-2, 0, 8, 12, 4],
               [0, 2, 1, 4, 6]])
KDT = np.array([[0, -2, -1, -4, -6],
                [2, 0, -8, -12, -4],
                [1, 8, 0, -8, -1],
                [4, 12, 8, 0, -2],
                [6, 4, 1, 2, 0]])
BANK = (KX, KY, KD, KDT)
R = 2


def _correlate(xp, k, oh, ow, dtype):
    import jax.numpy as jnp

    acc = None
    for i in range(5):
        for j in range(5):
            if k[i, j] == 0:
                continue
            term = jnp.asarray(k[i, j], dtype) * xp[..., i:i + oh, j:j + ow]
            acc = term if acc is None else acc + term
    return acc


def _components(img, ring, dtype):
    """The four responses over the image grown by ``ring`` on each side."""
    import jax.numpy as jnp

    h, w = img.shape[-2:]
    pad = [(0, 0)] * (img.ndim - 2) + [(R + ring, R + ring)] * 2
    xp = jnp.pad(img.astype(dtype), pad, mode="reflect")
    return [_correlate(xp, k, h + 2 * ring, w + 2 * ring, dtype) for k in BANK]


def _magnitude(comps):
    import jax.numpy as jnp

    # Each square is rounded to the working precision before the sum.
    # ``maximum(s, 0)`` is exact for a square and keeps the compiler from
    # fusing the square into the following add.
    squares = [jnp.maximum(g * g, jnp.zeros((), g.dtype)) for g in comps]
    acc = squares[0]
    for s in squares[1:]:
        acc = acc + s
    return jnp.sqrt(acc).astype(jnp.float32)


def _normalise(mag, peak):
    import jax.numpy as jnp

    return mag * (255.0 / jnp.maximum(peak, 1e-8))


@functools.lru_cache(maxsize=None)
def magnitude_program(dtype_name: str):
    """jit: (B, H, W) frames -> (served magnitude, peak (B,))."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def run(frames):
        mag = _magnitude(_components(frames, 0, dtype))
        peak = jnp.max(mag, axis=(-2, -1), keepdims=True)
        return _normalise(mag, peak), peak[..., 0, 0]

    return jax.jit(run)


def _shift(a, dy, dx):
    """``a[y + dy, x + dx]`` over the centre of an array grown by one."""
    h, w = a.shape[-2] - 2, a.shape[-1] - 2
    return a[..., 1 + dy:1 + dy + h, 1 + dx:1 + dx + w]


def _grow8(m):
    import jax.numpy as jnp

    p = jnp.pad(m, [(0, 0)] * (m.ndim - 2) + [(1, 1), (1, 1)])
    out = m
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            out = out | _shift(p, dy, dx)
    return out


@functools.lru_cache(maxsize=None)
def edges_program(dtype_name: str):
    """jit: (B, H, W) frames -> (served thin magnitude, edges, peak)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def run(frames):
        comps = _components(frames, 1, dtype)
        mag = _magnitude(comps)
        centre = [jnp.abs(_shift(g, 0, 0)).astype(jnp.float32) for g in comps]
        direction = jnp.argmax(jnp.stack(centre), axis=0)
        c = _shift(mag, 0, 0)
        neighbours = ((0, -1), (-1, 0), (-1, -1), (-1, 1))
        keep = jnp.zeros(c.shape, bool)
        for d, (dy, dx) in enumerate(neighbours):
            ok = (c >= _shift(mag, dy, dx)) & (c >= _shift(mag, -dy, -dx))
            keep = jnp.where(direction == d, ok, keep)
        thin = jnp.where(keep, c, 0.0)
        peak = jnp.max(c, axis=(-2, -1), keepdims=True)
        weak = thin > peak * jnp.float32(LOW)
        strong = (thin > peak * jnp.float32(HIGH)) & weak

        def grow(state):
            edges, _ = state
            new = _grow8(edges) & weak
            return new, jnp.any(new != edges)

        edges, _ = jax.lax.while_loop(lambda s: s[1], grow,
                                      (strong, jnp.bool_(True)))
        return _normalise(thin, peak), edges, peak[..., 0, 0]

    return jax.jit(run)


def outputs(frames, *, edges: bool, dtype: str = "float32") -> dict:
    """The served outputs of ``frames`` (B, H, W), as device arrays."""
    if edges:
        mag, e, peak = edges_program(dtype)(frames)
        return {"magnitude": mag, "edges": e, "peak": peak}
    mag, peak = magnitude_program(dtype)(frames)
    return {"magnitude": mag, "peak": peak}
