"""Spatial partitioning of frames across devices with halo exchange.

The paper's kernel wins by keeping the stencil halo in registers at warp
level; this module solves the same problem one level up, where a frame is
too big for one device. A frame is split into ``rows x cols`` spatial bands
over the image mesh ``(data, row, col)`` and each device computes its band
with a halo of ``OperatorSpec.radius`` pixels exchanged from its neighbors
— the device-level analogue of the in-kernel ``pl.Element`` halo windows
(``repro.kernels.tiling``).

Exactness contract — per-shard outputs are **bit-identical** to the
single-device engine:

  * Interior shard edges: ``jax.lax.ppermute`` carries each neighbor's
    ``r`` boundary rows/cols (one hop, non-cyclic — devices at the mesh
    ends receive zeros). A kept output pixel then reads exactly the same
    f32 values it would read on one device, and every downstream tap is
    FMA-proofed (``core.sobel``), so the arithmetic is identical.
  * Global image edges: the shard that owns the edge rebuilds the boundary
    extension *locally* from its own rows with the same
    ``reflect``/``edge``/``zero`` index map the kernels use
    (``tiling.boundary_index``), replacing the zeros the ppermute shift
    delivered there.
  * Ragged shapes: a dimension that does not divide the spatial grid is
    extended (before ``shard_map``) with materialized boundary-extension
    values, sized so that every *valid* output pixel reads only real image
    or extension values — the per-shard kernel's own boundary handling only
    ever touches halo outputs that are cropped away.
  * Normalization: the per-image peak is a masked per-shard ``max`` +
    ``lax.pmax`` over the spatial axes — max-of-maxes is exact.

The per-shard compute is a closure over the *existing* single-device engine
(the fused Pallas megakernel or the XLA reference — both run unchanged
under ``shard_map``), so cross-backend bit-exactness carries over to the
sharded paths by construction.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.tiling import PAD_MODES, boundary_index, window_radius
from repro.runtime.elastic import make_image_mesh, plan_image_mesh

__all__ = [
    "ShardConfig",
    "shard_geometry",
    "extend_axis",
    "halo_exchange",
    "sharded_edge",
    "mesh_from_config",
]


@dataclasses.dataclass(frozen=True)
class ShardConfig:
    """How to spread one edge-detection call over the image mesh.

    Fields:
      data: batch-axis shards (frames per device group); 0 = auto — fill
            whatever devices the spatial grid leaves over.
      rows: spatial row bands per frame (halo exchange along ``row``).
      cols: spatial column bands per frame (halo exchange along ``col``).

    The (data, rows, cols) -> mesh-axis placement is the image rule table
    (``sharding.rules.IMAGE_RULES``: batch -> data, height -> row,
    width -> col). ``ShardConfig()`` (all defaults) on a multi-device host
    means pure batch parallelism over every device. Hashable static config,
    like :class:`repro.api.EdgeConfig` itself.
    """

    data: int = 0
    rows: int = 1
    cols: int = 1

    @classmethod
    def auto(cls) -> "ShardConfig":
        """Fill all local devices with batch parallelism."""
        return cls(data=0, rows=1, cols=1)

    @classmethod
    def parse(cls, text: str) -> "ShardConfig":
        """``"DxRxC"`` (e.g. ``"2x2x2"``, ``0`` = auto-fill data) or
        ``"auto"``."""
        text = text.strip().lower()
        if text in ("auto", ""):
            return cls.auto()
        parts = text.split("x")
        if len(parts) != 3:
            raise ValueError(
                f"shard spec {text!r} must be 'DxRxC' (e.g. '2x2x2') or 'auto'"
            )
        d, r, c = (int(p) for p in parts)
        return cls(data=d, rows=r, cols=c)

    def resolve(self, n_devices: int) -> Tuple[int, int, int]:
        """Concrete (data, rows, cols) for ``n_devices``; raises if the
        explicit request does not fit. Only ``data`` may be 0 (= auto)."""
        if self.rows < 1 or self.cols < 1 or self.data < 0:
            raise ValueError(
                f"invalid shard config {self.data}x{self.rows}x{self.cols}: "
                "rows/cols must be >= 1 (only data may be 0 = auto-fill)"
            )
        if self.rows * self.cols > n_devices:
            raise ValueError(
                f"spatial grid {self.rows}x{self.cols} needs "
                f"{self.rows * self.cols} devices, have {n_devices}"
            )
        (d, r, c), _ = plan_image_mesh(
            n_devices, rows=self.rows, cols=self.cols, data=self.data
        )
        if self.data and d != self.data:
            raise ValueError(
                f"shard config {self.data}x{self.rows}x{self.cols} needs "
                f"{self.data * self.rows * self.cols} devices, have {n_devices}"
            )
        return d, r, c


def mesh_from_config(
    shard: ShardConfig, devices: Optional[Sequence] = None
) -> Mesh:
    """Concrete image mesh for a :class:`ShardConfig` (default: all local
    devices)."""
    devices = list(devices if devices is not None else jax.devices())
    d, r, c = shard.resolve(len(devices))
    return make_image_mesh(devices, rows=r, cols=c, data=d)


# ---------------------------------------------------------------------------
# Shard geometry + materialized boundary extension (outside shard_map)
# ---------------------------------------------------------------------------

def exchange_radius(spec, nms: bool = False, *, plan=None) -> int:
    """Halo-exchange width (px) for one fused step of ``spec``.

    Delegates to :func:`repro.kernels.tiling.window_radius` so the
    cross-device exchange is sized by the same rule as the in-VMEM kernel
    window — the HALO001 invariant checked by ``repro.analysis``. A
    multi-stage ``plan`` composes the radii of every linear stage
    (``plan.linear_reach``) plus the NMS ring, so one exchange covers the
    whole fused chain.
    """
    if plan is not None:
        return window_radius(plan.linear_reach, nms or plan.nms)
    return window_radius(spec.radius, nms)


def shard_geometry(n: int, parts: int, radius: int) -> Tuple[int, int]:
    """(shard, padded_total) for one spatial dim split into ``parts``.

    Unsharded dims pass through. Sharded dims are padded up to
    ``parts * shard`` with ``shard = ceil((n + radius) / parts)`` — always
    at least ``radius`` rows of slack past the true edge, so a valid output
    pixel (global coordinate < n) never reads past the materialized
    extension into a neighborless halo (see :func:`sharded_edge`).
    """
    if parts <= 1:
        return n, n
    shard = -(-(n + radius) // parts)
    return shard, shard * parts


def extend_axis(
    x: jnp.ndarray, axis: int, n: int, total: int, padding: str
) -> jnp.ndarray:
    """Extend ``x`` from ``n`` to ``total`` along ``axis`` with the boundary
    rule's extension values (the same index map the kernels apply
    in-kernel, so the materialized pad is bit-identical to what the
    single-device kernel would synthesize)."""
    if total == n:
        return x
    g = jnp.arange(n, total)
    pad = jnp.take(x, boundary_index(g, n, padding), axis=axis)
    if padding == "zero":
        pad = jnp.zeros_like(pad)
    return jnp.concatenate([x, pad], axis=axis)


# ---------------------------------------------------------------------------
# Halo exchange (inside shard_map)
# ---------------------------------------------------------------------------

def halo_exchange(
    x: jnp.ndarray,
    radius: int,
    padding: str,
    *,
    axis: int,
    axis_name: str,
    parts: int,
    n_global: int,
) -> jnp.ndarray:
    """One spatial dim of halo exchange: grow the local block by ``radius``
    on both sides along ``axis``.

    Interior halos come from the neighbors via two non-cyclic
    ``lax.ppermute`` shifts; the first shard then overwrites its (zero-
    filled) leading halo with the locally rebuilt boundary extension. The
    last shard's trailing halo stays zero-filled — by construction
    (:func:`shard_geometry`) no valid output ever reads it.
    """
    if parts <= 1:
        return x
    if padding not in PAD_MODES:
        raise ValueError(f"unknown padding {padding!r}; expected one of {PAD_MODES}")
    size = x.shape[axis]
    lo = jax.lax.slice_in_dim(x, 0, radius, axis=axis)
    hi = jax.lax.slice_in_dim(x, size - radius, size, axis=axis)
    fwd = [(i, i + 1) for i in range(parts - 1)]
    bwd = [(i + 1, i) for i in range(parts - 1)]
    lead = jax.lax.ppermute(hi, axis_name, fwd)   # neighbor above's last rows
    trail = jax.lax.ppermute(lo, axis_name, bwd)  # neighbor below's first rows
    if padding != "zero":  # zero extension == the zeros ppermute delivered
        # the exact index map the kernels apply in-kernel; trace-time constant
        src = boundary_index(jnp.arange(-radius, 0), n_global, padding)
        fixed = jnp.take(x, src, axis=axis)
        lead = jnp.where(jax.lax.axis_index(axis_name) == 0, fixed, lead)
    return jnp.concatenate([lead, x, trail], axis=axis)


# ---------------------------------------------------------------------------
# The sharded engine
# ---------------------------------------------------------------------------

def sharded_edge(
    x: jnp.ndarray,
    mesh: Mesh,
    *,
    radius: int,
    padding: str,
    compute: Callable[[jnp.ndarray], Tuple[jnp.ndarray, Optional[jnp.ndarray]]],
    rgb: bool = False,
    need_comps: bool = False,
    need_peak: bool = False,
    chaos=None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """Run a per-shard edge compute over the image mesh, bit-exact with the
    single-device engine.

    Args:
      x: ``(B, H, W)`` grayscale or ``(B, H, W, 3)`` RGB batch (u8/f32).
      mesh: image mesh with axes ``("data", "row", "col")``.
      radius: device-level halo radius — ``OperatorSpec.radius``, plus one
        when the per-shard compute appends the NMS stage (its magnitude
        neighborhood needs the extra ring; see ``kernels.dispatch``).
      padding: boundary rule — also governs halo fixup at global edges.
      compute: per-shard single-device engine: takes the halo-extended local
        block ``(B_loc, h_ext, w_ext[, 3])``, returns ``(primary,
        components-or-None, raw-magnitude-or-None)`` with components shaped
        ``(B_loc, D, h_ext, w_ext)``. ``primary`` is the magnitude — or the
        NMS thin map, in which case the third element carries the un-thinned
        magnitude as the peak source (``None`` = reduce the primary).
      need_comps / need_peak: which extras to assemble.
      chaos: optional ``repro.runtime.chaos.FaultPlan``; fires the
        ``"halo.sharded_edge"`` injection site before the shard_map launch
        (host-side — at trace time under ``jax.jit``).

    Returns:
      ``(primary (B, H, W), components (B, D, H, W) | None,
      peak (B,) | None)`` — the peak is the exact per-image max of the
      unnormalized magnitude over valid pixels.
    """
    if chaos is not None:
        chaos.fire("halo.sharded_edge")
    d = mesh.shape["data"]
    rr = mesh.shape["row"]
    cc = mesh.shape["col"]
    b = x.shape[0]
    h, w = (x.shape[-3], x.shape[-2]) if rgb else (x.shape[-2], x.shape[-1])

    sh, hp = shard_geometry(h, rr, radius)
    sw, wp = shard_geometry(w, cc, radius)
    for name, parts, shard in (("rows", rr, sh), ("cols", cc, sw)):
        if parts > 1 and shard < radius + 1:
            raise ValueError(
                f"{name}={parts} leaves spatial shards of {shard} pixels — "
                f"too small for operator radius {radius}; use a coarser "
                "spatial grid for this image"
            )

    # Materialize extension values (ragged pad) and round the batch up.
    bp = -(-b // d) * d
    if bp != b:
        x = jnp.concatenate(
            [x, jnp.zeros((bp - b,) + x.shape[1:], x.dtype)], axis=0
        )
    x = extend_axis(x, 1, h, hp, padding)
    x = extend_axis(x, 2, w, wp, padding)

    t = radius if rr > 1 else 0  # leading halo after exchange
    l = radius if cc > 1 else 0

    def per_shard(xl):
        ext = halo_exchange(
            xl, radius, padding, axis=1, axis_name="row", parts=rr, n_global=h
        )
        ext = halo_exchange(
            ext, radius, padding, axis=2, axis_name="col", parts=cc, n_global=w
        )
        mag, comps, raw = compute(ext)
        nb = mag.shape[0]
        mag = jax.lax.slice(mag, (0, t, l), (nb, t + sh, l + sw))
        out = [mag]
        if need_comps:
            nd = comps.shape[1]
            comps = jax.lax.slice(
                comps, (0, 0, t, l), (nb, nd, t + sh, l + sw)
            )
            out.append(comps)
        if need_peak:
            src = mag
            if raw is not None:  # NMS mode: peak of the un-thinned magnitude
                src = jax.lax.slice(raw, (0, t, l), (nb, t + sh, l + sw))
            gr = jax.lax.axis_index("row") * sh + jnp.arange(sh) < h
            gc = jax.lax.axis_index("col") * sw + jnp.arange(sw) < w
            valid = gr[:, None] & gc[None, :]
            # magnitude >= 0, so masking invalid cells to 0 is exact
            peak = jnp.max(jnp.where(valid, src, jnp.float32(0.0)), axis=(1, 2))
            out.append(jax.lax.pmax(peak, ("row", "col")))
        return tuple(out)

    in_spec = P("data", "row", "col", None) if rgb else P("data", "row", "col")
    out_specs = [P("data", "row", "col")]
    if need_comps:
        out_specs.append(P("data", None, "row", "col"))
    if need_peak:
        out_specs.append(P("data"))

    outs = jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(in_spec,),
        out_specs=tuple(out_specs),
        check_vma=False,
    )(x)

    outs = list(outs)
    mag = outs.pop(0)[:b, :h, :w]
    comps = outs.pop(0)[:b, :, :h, :w] if need_comps else None
    peak = outs.pop(0)[:b] if need_peak else None
    return mag, comps, peak
