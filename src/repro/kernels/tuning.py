"""Block-shape autotuner for the Pallas Sobel kernels (paper Fig. 6).

The paper's key tuning knob is the CUDA block geometry; ours is the Pallas
``(block_h, block_w)`` tile. This module:

  * enumerates *legal* block shapes for an image/operator/backend
    (:func:`legal_block_shapes`),
  * times each one with the same harness the benchmark suites use
    (:func:`measure_us` — warm call to exclude compile, then a best-of-iters
    loop), and
  * persists the winner in a JSON cache keyed by
    ``(backend, dtype, operator, variant, padding, layout, H, W, devices,
    mesh, precision, plan)`` (:class:`TuningCache`), which
    ``repro.kernels.dispatch`` consults on every call. Every segment names
    something that changes the kernel's tiling or arithmetic: the operator
    (its halo radius), padding and gray/RGB layout, the device count and
    image mesh (a sharded kernel tiles the halo-extended *local* block),
    the resolved lane (an integer-lane tuning has other VMEM pressure and
    arithmetic than the f32 one) and the plan identity
    (``repro.core.filters.plan_identity`` — name + a stable hash of the
    stage structure — or ``-`` for plain single-operator calls; a fused
    plan tiles a larger composed halo). The file carries a schema version
    (now 7); a file of any other schema is skipped with a warning, and the
    tunings it held are measured again on demand.

Cache location: ``$REPRO_TUNE_CACHE`` if set, else
``~/.cache/repro/sobel_blocks.json``. The file is plain JSON so it can be
committed, diffed, and shipped with a deployment image.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.kernels.tiling import halo_amplification, tile_vmem_bytes

__all__ = [
    "TuneKey",
    "TuningCache",
    "default_cache_path",
    "measure_us",
    "legal_block_shapes",
    "sweep",
    "autotune",
    "get_default_cache",
]

# Per-core VMEM budget used to reject obviously-oversized tiles (bytes).
VMEM_BUDGET = 16 * 1024 * 1024

# Candidate grids. TPU lane width is 128 and the f32 sublane tile is 8, so
# the hardware backend restricts to multiples of (8, 128); interpret mode
# (and the tests) may go smaller.
_CAND_H = (8, 16, 32, 64, 128, 256)
_CAND_W = (32, 64, 128, 256, 512, 1024)


@dataclasses.dataclass(frozen=True)
class TuneKey:
    """Cache key: one tuned workload shape."""

    backend: str      # pallas-tpu | pallas-interpret
    dtype: str        # canonical jnp dtype name of the *input* image
    operator: str     # registered operator name (sobel5 | sobel3 | scharr3 | ...)
    variant: str
    h: int            # frame H/W as the user sees it (not the local block)
    w: int
    padding: str = "reflect"   # reflect | edge | zero
    layout: str = "gray"       # gray | rgb
    devices: int = 1           # devices the call spans (1 = single-device)
    mesh: str = "1x1x1"        # image mesh shape "DxRxC" (data x row x col)
    precision: str = "f32"     # resolved lane: f32 | int
    plan: str = "-"            # plan identity (filters.plan_identity) or "-"

    def to_str(self) -> str:
        return (
            f"{self.backend}/{self.dtype}/{self.operator}/{self.variant}"
            f"/{self.padding}/{self.layout}/{self.h}x{self.w}"
            f"/{self.devices}/{self.mesh}/{self.precision}/{self.plan}"
        )


@contextlib.contextmanager
def _file_lock(path: str):
    """Advisory exclusive lock on ``path`` (created on demand).

    ``flock`` attaches to the open file description, so every locker —
    process or thread — opens its own handle and they serialize. On
    platforms without ``fcntl`` this degrades to no lock: saves stay
    atomic (temp + rename), they just lose merge-with-peers.
    """
    try:
        import fcntl
    except ImportError:  # pragma: no cover — non-POSIX best effort
        yield
        return
    with open(path, "a") as lk:
        fcntl.flock(lk, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lk, fcntl.LOCK_UN)


class TuningCache:
    """JSON-backed best-known-config store.

    Schema: ``{key: {"block_h": int, "block_w": int, "us": float}}`` with
    a ``__meta__`` entry recording the schema version. A file of another
    schema (or with no ``__meta__``) is skipped with a warning: its key
    layout is not this one's.
    """

    VERSION = 7

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self._entries: Dict[str, Dict] = {}
        self.load()

    @staticmethod
    def _valid_entry(value) -> bool:
        """A usable cache entry: a dict with positive-int-able block dims."""
        if not isinstance(value, dict):
            return False
        try:
            return int(value["block_h"]) > 0 and int(value["block_w"]) > 0
        except (KeyError, TypeError, ValueError):
            return False

    def load(self) -> "TuningCache":
        """Load the cache file; never raises.

        A tuning cache is an optional accelerant, so a bad file must not
        take ``edge_detect`` down: unreadable/truncated JSON, a non-dict
        payload, a file of another schema version (an older or newer
        deployment's file on a shared path), and individually corrupted
        entries are all skipped with a warning rather than raised.
        """
        self._entries = {}
        try:
            with open(self.path) as f:
                raw = json.load(f)
        except FileNotFoundError:
            return self
        except (json.JSONDecodeError, OSError, UnicodeDecodeError) as e:
            warnings.warn(
                f"ignoring unreadable tuning cache {self.path}: {e}",
                RuntimeWarning, stacklevel=2,
            )
            return self
        if not isinstance(raw, dict):
            warnings.warn(
                f"ignoring tuning cache {self.path}: expected a JSON object, "
                f"got {type(raw).__name__}",
                RuntimeWarning, stacklevel=2,
            )
            return self
        meta = raw.get("__meta__")
        version = meta.get("version") if isinstance(meta, dict) else None
        if version != self.VERSION:
            # Another schema's key layout is not this one's — dropping the
            # entries (tunings re-measure on demand) beats misreading them.
            warnings.warn(
                f"ignoring tuning cache {self.path}: schema version "
                f"{version!r} is not the supported {self.VERSION}; run with "
                "a matching build or delete the file",
                RuntimeWarning, stacklevel=2,
            )
            return self
        entries = {k: v for k, v in raw.items() if not k.startswith("__")}
        bad = [k for k, v in entries.items() if not self._valid_entry(v)]
        if bad:
            warnings.warn(
                f"skipping {len(bad)} corrupted tuning cache entr"
                f"{'y' if len(bad) == 1 else 'ies'} in {self.path} "
                f"(e.g. {bad[0]!r})",
                RuntimeWarning, stacklevel=2,
            )
        self._entries = {k: v for k, v in entries.items() if k not in set(bad)}
        return self

    def save(self) -> None:
        """Atomically persist the cache, merging concurrent writers.

        The write itself was always torn-file-proof (write-temp +
        ``os.replace``), but two serving processes doing read-modify-write
        could still lose each other's tunings — last replace wins. Under an
        advisory lock on a ``.lock`` sidecar (``flock`` binds to the open
        file description, so concurrent threads serialize too), the saver
        re-reads the file and merges entry-by-entry: a key present on both
        sides keeps the *faster* measured tuning, so the cache only ever
        improves regardless of writer interleaving. The merge result also
        replaces the in-memory view, so a saver sees its peers' entries.
        """
        os.makedirs(os.path.dirname(os.path.abspath(self.path)), exist_ok=True)
        with _file_lock(f"{self.path}.lock"):
            on_disk = dict(TuningCache(self.path)._entries)
            for k, v in self._entries.items():
                cur = on_disk.get(k)
                if cur is None or not self._valid_entry(cur) or (
                    float(v.get("us", float("inf")))
                    <= float(cur.get("us", float("inf")))
                ):
                    on_disk[k] = v
            self._entries = on_disk
            payload = {"__meta__": {"version": self.VERSION}}
            payload.update(dict(sorted(self._entries.items())))
            tmp = f"{self.path}.tmp.{os.getpid()}.{id(self)}"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=2)
                f.write("\n")
            os.replace(tmp, self.path)

    def lookup(self, key: TuneKey) -> Optional[Tuple[int, int]]:
        """(block_h, block_w) for the key, or None."""
        e = self._entries.get(key.to_str())
        if not e:
            return None
        if not self._valid_entry(e):  # belt-and-braces: entries set post-load
            warnings.warn(
                f"skipping corrupted tuning cache entry {key.to_str()!r} "
                f"in {self.path}",
                RuntimeWarning, stacklevel=2,
            )
            return None
        return int(e["block_h"]), int(e["block_w"])

    def record(self, key: TuneKey, block_h: int, block_w: int, us: float) -> None:
        self._entries[key.to_str()] = {
            "block_h": int(block_h),
            "block_w": int(block_w),
            "us": float(us),
        }

    def __len__(self) -> int:
        return len(self._entries)


def default_cache_path() -> str:
    env = os.environ.get("REPRO_TUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro", "sobel_blocks.json")


_DEFAULT_CACHE: Optional[TuningCache] = None


def get_default_cache() -> TuningCache:
    """Process-wide cache singleton (lazily loaded from disk)."""
    global _DEFAULT_CACHE
    if _DEFAULT_CACHE is None or _DEFAULT_CACHE.path != default_cache_path():
        _DEFAULT_CACHE = TuningCache()
    return _DEFAULT_CACHE


# ---------------------------------------------------------------------------
# Timing harness (shared with benchmarks/)
# ---------------------------------------------------------------------------

def measure_us(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    """Best-of-``iters`` wall time per call in microseconds, after
    ``warmup`` calls (compile + cache warm). Best-of, not mean: the minimum
    is the standard de-noised microbenchmark statistic (scheduler and
    frequency jitter only ever add time), which keeps the
    ``benchmarks/run.py --compare`` regression gate stable. This is the
    harness all benchmark suites use."""
    # $REPRO_BENCH_ITERS raises the floor on noisy/shared hosts (CI sets it
    # for the --compare regression gate).
    iters = max(iters, int(os.environ.get("REPRO_BENCH_ITERS", "0") or 0))
    out = None
    for _ in range(max(1, warmup)):
        out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# ---------------------------------------------------------------------------
# Shape enumeration + sweep
# ---------------------------------------------------------------------------

def _operator_size(operator: Optional[str], size: int) -> int:
    """Halo geometry for a key: the spec's size when ``operator`` is given."""
    if operator is None:
        return size
    from repro.core.filters import get_operator

    return get_operator(operator).size


def legal_block_shapes(
    h: int,
    w: int,
    *,
    size: int = 5,
    operator: Optional[str] = None,
    backend: str = "pallas-interpret",
    layout: str = "gray",
    max_vmem_bytes: int = VMEM_BUDGET,
) -> List[Tuple[int, int]]:
    """All (block_h, block_w) candidates legal for an HxW image.

    The fused zero-copy kernels put no divisibility constraints on the block
    (clamped windows + in-kernel masking handle ragged grids), so legality is
    only: not wastefully larger than the image, fits the VMEM budget (the
    RGB megakernel's input window is 3x the grayscale one — ``layout``), and
    — on the hardware backend — the f32 (8, 128) tile so Mosaic gets aligned
    output blocks. ``operator`` (registry name) overrides ``size`` for the
    halo geometry.
    """
    r = _operator_size(operator, size) // 2
    channels = 3 if layout == "rgb" else None
    shapes = []
    for bh in _CAND_H:
        for bw in _CAND_W:
            if backend == "pallas-tpu" and (bh % 8 or bw % 128):
                continue
            # Bigger than the image in either dim is just the smaller sweep
            # point plus padding waste; keep the smallest such block only.
            if (bh >= 2 * h and bh != _CAND_H[0]) or (bw >= 2 * w and bw != _CAND_W[0]):
                continue
            if tile_vmem_bytes(bh, bw, r, channels=channels) > max_vmem_bytes:
                continue
            shapes.append((bh, bw))
    return shapes


def _run_shape(
    img, operator, variant, directions, padding, backend, bh, bw,
    precision="f32", plan=None,
):
    from repro.core.filters import resolve_plan
    from repro.kernels.edge import edge_pallas

    plan = resolve_plan(plan)
    rgb = img.ndim >= 3 and img.shape[-1] == 3
    return edge_pallas(
        img,
        operator=operator,
        directions=directions,
        variant=variant,
        padding=padding,
        block_h=bh,
        block_w=bw,
        rgb=rgb,
        precision=precision,
        plan=plan,
        out_nms=plan.nms if plan is not None else False,
        interpret=(backend != "pallas-tpu"),
    )


def sweep(
    h: int,
    w: int,
    *,
    size: int = 5,
    operator: Optional[str] = None,
    variant: str = "v2",
    directions: int = 0,   # 0 = operator max
    dtype: str = "float32",
    backend: str = "pallas-interpret",
    padding: str = "reflect",
    layout: str = "gray",
    shapes: Optional[Sequence[Tuple[int, int]]] = None,
    iters: int = 3,
    seed: int = 0,
    precision: str = "f32",
    plan=None,
) -> List[Dict]:
    """Time every candidate block shape on a random HxW image.

    Returns one row per shape: ``{"block_h", "block_w", "us",
    "vmem_bytes", "halo_overhead", "grid_steps"}`` — the structural
    columns of the paper's Fig. 6 sweep, generalized to both block
    dimensions. ``layout="rgb"`` times the full fused gray->Sobel
    megakernel on an ``(1, h, w, 3)`` frame. ``operator`` (registry name)
    overrides the legacy ``size`` selector; ``plan`` (a
    :class:`~repro.core.filters.StencilPlan` or registered plan name)
    overrides both and times the fused multi-stage kernel with its
    composed halo. ``precision="int"`` times the
    exact integer lane — pass ``dtype="uint8"`` or ``"uint16"`` with it
    (the lane rejects anything else).
    """
    import jax.numpy as jnp

    from repro.core.filters import get_operator, operator_for_size, resolve_plan

    plan = resolve_plan(plan)
    if plan is not None:
        spec = plan.gradient
        if spec is None:
            raise ValueError(
                f"plan {plan.name!r} has no gradient stage; the edge kernel "
                "sweep needs one"
            )
        operator = spec.name
        r = plan.reach
    else:
        operator = operator or operator_for_size(size)
        spec = get_operator(operator)
        r = spec.radius
    variant = spec.resolve_variant(variant)
    directions = spec.resolve_directions(directions)
    channels = 3 if layout == "rgb" else None
    if shapes is None:
        shapes = legal_block_shapes(
            h, w, size=2 * r + 1,
            operator=None if plan is not None else operator,
            backend=backend, layout=layout,
        )
    rng = np.random.default_rng(seed)
    shape = (1, h, w, 3) if layout == "rgb" else (1, h, w)
    img = jnp.asarray(rng.integers(0, 256, shape).astype(dtype))
    rows = []
    for bh, bw in shapes:
        us = measure_us(
            _run_shape, img, operator, variant, directions, padding,
            backend, bh, bw, precision, plan, iters=iters,
        )
        gh, gw = -(-h // bh), -(-w // bw)
        rows.append(
            {
                "block_h": bh,
                "block_w": bw,
                "us": us,
                "vmem_bytes": tile_vmem_bytes(bh, bw, r, channels=channels),
                "halo_overhead": halo_amplification(bh, bw, r),
                "grid_steps": gh * gw,
            }
        )
    return rows


def autotune(
    h: int,
    w: int,
    *,
    size: int = 5,
    operator: Optional[str] = None,
    variant: str = "v2",
    directions: int = 0,   # 0 = operator max
    dtype: str = "float32",
    backend: str = "pallas-interpret",
    padding: str = "reflect",
    layout: str = "gray",
    shapes: Optional[Sequence[Tuple[int, int]]] = None,
    iters: int = 3,
    cache: Optional[TuningCache] = None,
    refresh: bool = False,
    save: bool = True,
    devices: int = 1,
    mesh: str = "1x1x1",
    precision: str = "f32",
    plan=None,
) -> Tuple[int, int]:
    """Best (block_h, block_w) for the workload; cached across processes.

    Consults ``cache`` (default: the process-wide JSON cache) unless
    ``refresh``; on a miss, sweeps the legal shapes, records the winner, and
    persists the cache to disk (``save=False`` to skip, e.g. in tests).
    ``operator`` (registry name) overrides the legacy ``size`` selector;
    ``plan`` (a :class:`~repro.core.filters.StencilPlan` or registered plan
    name) overrides both — the tuning times the fused multi-stage kernel
    and lands in the plan-identity cache slot.
    ``devices``/``mesh`` slot the tuning for a sharded deployment — the
    sweep itself times the per-shard (h, w) block, which for a spatial mesh
    is the halo-extended local shape (see ``dispatch.choose_block_shape``).

    ``precision`` keys (and times) the resolved arithmetic lane.
    """
    from repro.core.filters import (
        get_operator, operator_for_size, plan_identity, resolve_plan,
    )

    plan = resolve_plan(plan)
    if plan is not None:
        spec = plan.gradient
        if spec is None:
            raise ValueError(
                f"plan {plan.name!r} has no gradient stage; the edge kernel "
                "autotune needs one"
            )
        operator = spec.name
        variant = spec.resolve_variant(variant)
        plan_seg = plan_identity(plan)
    else:
        operator = operator or operator_for_size(size)
        # Key on the *resolved* variant so the slot matches what actually
        # ran (e.g. scharr3 has no diagonal transform: v2 -> separable).
        variant = get_operator(operator).resolve_variant(variant)
        plan_seg = "-"
    cache = cache if cache is not None else get_default_cache()
    key = TuneKey(backend, dtype, operator, variant, h, w, padding, layout,
                  devices, mesh, precision, plan_seg)
    if not refresh:
        hit = cache.lookup(key)
        if hit is not None:
            return hit
    rows = sweep(
        h, w, operator=operator, variant=variant, directions=directions,
        dtype=dtype, backend=backend, padding=padding, layout=layout,
        shapes=shapes, iters=iters, precision=precision, plan=plan,
    )
    if not rows:
        raise ValueError(f"no legal block shapes for {key.to_str()}")
    best = min(rows, key=lambda r: r["us"])
    cache.record(key, best["block_h"], best["block_w"], best["us"])
    if save:
        cache.save()
    return best["block_h"], best["block_w"]
