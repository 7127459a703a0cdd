"""Block autotuner (JSON cache round-trip) + unified backend dispatch.

No optional deps (runs without hypothesis).
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sobel import sobel as core_sobel
from repro.kernels import dispatch, tuning


def _img(rng, shape):
    return jnp.asarray(rng.integers(0, 256, size=shape).astype(np.float32))


def _dsobel(img, *, tuning_cache=None, **cfg_kw):
    """dispatch.edge magnitude with the historical ``sobel()`` defaults
    (unnormalized, gray layout inferred from rank)."""
    from repro.api import EdgeConfig

    layout = "N" * max(0, img.ndim - 2) + "HW"
    return dispatch.edge(
        img, EdgeConfig(normalize=False, **cfg_kw), layout=layout,
        tuning_cache=tuning_cache,
    ).magnitude


# ---------------------------------------------------------------------------
# Legal shape enumeration
# ---------------------------------------------------------------------------

def test_legal_shapes_unconstrained_on_interpret():
    # The fused kernels have no divisibility constraints (clamped windows +
    # in-kernel masking): every candidate that fits VMEM is legal.
    for size in (5, 3):
        shapes = tuning.legal_block_shapes(256, 256, size=size)
        assert shapes
        assert (8, 32) in shapes  # smallest candidate survives
        for bh, bw in shapes:
            assert bh >= 1 and bw >= 1


def test_legal_shapes_tpu_alignment():
    shapes = tuning.legal_block_shapes(1024, 1024, size=5, backend="pallas-tpu")
    assert shapes
    for bh, bw in shapes:
        assert bh % 8 == 0 and bw % 128 == 0


def test_legal_shapes_respect_vmem_budget():
    shapes = tuning.legal_block_shapes(8192, 8192, size=5, max_vmem_bytes=64 * 1024)
    for bh, bw in shapes:
        assert tuning.tile_vmem_bytes(bh, bw, 2) <= 64 * 1024


def test_measure_us_positive():
    us = tuning.measure_us(lambda x: x + 1, jnp.ones((8, 8)), iters=2)
    assert us > 0


def _key(i):
    return tuning.TuneKey("pallas-interpret", "float32", "sobel5", "v2",
                          64 + i, 64)


def test_save_merges_concurrent_writers(tmp_path):
    """Lost-update regression: N writers, each holding its own cache view
    of the same file, record distinct keys and save concurrently. Every
    key must survive — save() merges with the file under the lock instead
    of blind last-replace-wins."""
    import threading

    path = str(tmp_path / "blocks.json")
    n = 8
    caches = [tuning.TuningCache(path) for _ in range(n)]
    for i, c in enumerate(caches):
        c.record(_key(i), 8, 32, us=100.0 + i)
    barrier = threading.Barrier(n)

    def writer(c):
        barrier.wait()
        c.save()

    threads = [threading.Thread(target=writer, args=(c,)) for c in caches]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    merged = tuning.TuningCache(path)
    assert len(merged) == n
    for i in range(n):
        assert merged.lookup(_key(i)) == (8, 32)
    with open(path) as f:
        assert json.load(f)["__meta__"]["version"] == tuning.TuningCache.VERSION


def test_save_merge_keeps_faster_tuning(tmp_path):
    """Two writers tuned the SAME key: the merge keeps the faster
    measurement whichever order the saves land in."""
    path = str(tmp_path / "blocks.json")
    slow = tuning.TuningCache(path)
    fast = tuning.TuningCache(path)
    slow.record(_key(0), 16, 64, us=500.0)
    fast.record(_key(0), 8, 32, us=50.0)
    slow.save()
    fast.save()
    assert tuning.TuningCache(path).lookup(_key(0)) == (8, 32)

    path2 = str(tmp_path / "blocks2.json")
    slow = tuning.TuningCache(path2)
    fast = tuning.TuningCache(path2)
    slow.record(_key(0), 16, 64, us=500.0)
    fast.record(_key(0), 8, 32, us=50.0)
    fast.save()
    slow.save()                     # slower result arrives second: ignored
    assert tuning.TuningCache(path2).lookup(_key(0)) == (8, 32)
    # and the losing saver's in-memory view was refreshed with the winner
    assert slow.lookup(_key(0)) == (8, 32)


# ---------------------------------------------------------------------------
# Autotune + cache round-trip
# ---------------------------------------------------------------------------

def test_autotune_cache_roundtrip(tmp_path, rng):
    """write -> reload -> dispatch picks the cached shape (the acceptance
    path for the tuning subsystem)."""
    path = str(tmp_path / "blocks.json")
    cache = tuning.TuningCache(path)
    shapes = [(8, 16), (16, 16)]
    bh, bw = tuning.autotune(32, 48, shapes=shapes, iters=1, cache=cache)
    assert (bh, bw) in shapes

    # The JSON on disk round-trips through a fresh cache object.
    raw = json.load(open(path))
    assert any(k.endswith("/32x48/1/1x1x1/f32/-")
               for k in raw if not k.startswith("__"))
    reloaded = tuning.TuningCache(path)
    key = tuning.TuneKey("pallas-interpret", "float32", "sobel5", "v2", 32, 48)
    assert reloaded.lookup(key) == (bh, bw)

    # A second autotune is a pure cache hit (no sweep: empty shape list ok).
    assert tuning.autotune(32, 48, shapes=[], iters=1, cache=reloaded) == (bh, bw)

    # Dispatch consults the cache...
    got = dispatch.choose_block_shape(32, 48, backend="pallas-interpret", cache=reloaded)
    assert got == (bh, bw, "tuned")
    # ...and produces the reference output with the tuned shape.
    img = _img(rng, (1, 32, 48))
    out = _dsobel(img, backend="pallas-interpret", tuning_cache=reloaded)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(core_sobel(img)))


def test_choose_block_shape_priority(tmp_path):
    cache = tuning.TuningCache(str(tmp_path / "c.json"))
    # no entry -> default
    bh, bw, src = dispatch.choose_block_shape(64, 512, backend="pallas-interpret", cache=cache)
    assert src == "default" and bh and bw
    # cached entry -> tuned
    cache.record(tuning.TuneKey("pallas-interpret", "float32", "sobel5", "v2", 64, 512), 16, 32, 1.0)
    assert dispatch.choose_block_shape(
        64, 512, backend="pallas-interpret", cache=cache
    ) == (16, 32, "tuned")
    # the resolved lane keys its own tuning slot: it does not see the f32
    # entry, and once tuned it returns its own blocks
    assert dispatch.choose_block_shape(
        64, 512, backend="pallas-interpret", cache=cache, precision="int"
    )[2] == "default"
    cache.record(
        tuning.TuneKey("pallas-interpret", "float32", "sobel5", "v2", 64, 512,
                       precision="int"), 8, 64, 1.0)
    assert dispatch.choose_block_shape(
        64, 512, backend="pallas-interpret", cache=cache, precision="int"
    ) == (8, 64, "tuned")
    # explicit args always win
    assert dispatch.choose_block_shape(
        64, 512, backend="pallas-interpret", cache=cache, block_h=8, block_w=8
    ) == (8, 8, "explicit")


def test_cache_ignores_corrupt_file(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text("{not json")
    with pytest.warns(RuntimeWarning, match="unreadable tuning cache"):
        cache = tuning.TuningCache(str(path))
    assert len(cache) == 0


def _cur_payload(**entries):
    payload = {"__meta__": {"version": tuning.TuningCache.VERSION}}
    payload.update(entries)
    return payload


_CUR_KEY = "pallas-interpret/float32/sobel5/v2/reflect/gray/64x64/1/1x1x1/f32/-"


def test_cache_from_the_future_skips_and_warns(tmp_path):
    """A newer schema's file (newer deployment, shared cache path) must not raise — and
    must not be misread either: its entries are dropped with a warning, and
    dispatch falls back to the default block shape."""
    path = tmp_path / "future.json"
    path.write_text(json.dumps({
        "__meta__": {"version": tuning.TuningCache.VERSION + 1},
        # plausible future key layout + value schema drift
        "pallas-tpu/float32/sobel5/v2/reflect/gray/64x64/1/1x1x1/f32/-/extra":
            {"block": [32, 128], "us": 1.0},
        _CUR_KEY: {"block_h": 8, "block_w": 32, "us": 1.0},
    }))
    with pytest.warns(RuntimeWarning, match="is not the supported"):
        cache = tuning.TuningCache(str(path))
    assert len(cache) == 0
    bh, bw, src = dispatch.choose_block_shape(
        64, 64, backend="pallas-interpret", cache=cache
    )
    assert src == "default" and bh > 0 and bw > 0


def test_cache_truncated_json_skips_and_warns(tmp_path):
    """A mid-write-truncated file (crash during a non-atomic copy) loads as
    empty with a warning instead of raising mid-edge_detect."""
    path = tmp_path / "trunc.json"
    full = json.dumps(_cur_payload(**{
        _CUR_KEY: {"block_h": 8, "block_w": 32, "us": 1.0}}))
    path.write_text(full[: len(full) // 2])
    with pytest.warns(RuntimeWarning, match="unreadable tuning cache"):
        cache = tuning.TuningCache(str(path))
    assert len(cache) == 0
    assert cache.lookup(tuning.TuneKey(
        "pallas-interpret", "float32", "sobel5", "v2", 64, 64)) is None


def test_cache_corrupted_entries_skipped_individually(tmp_path):
    """One bad entry (wrong value shape / non-numeric blocks) must not sink
    the healthy ones."""
    good_key = _CUR_KEY
    bad_keys = {
        "pallas-interpret/float32/sobel5/v2/reflect/gray/32x32/1/1x1x1/f32/-":
            {"block": "8x32"},                      # missing block_h/block_w
        "pallas-interpret/float32/sobel5/v2/reflect/gray/16x16/1/1x1x1/f32/-":
            {"block_h": "eight", "block_w": 32},    # non-numeric
        "pallas-interpret/float32/sobel5/v2/reflect/gray/8x8/1/1x1x1/f32/-":
            [8, 32],                                # not a dict
    }
    path = tmp_path / "mixed.json"
    path.write_text(json.dumps(_cur_payload(
        **{good_key: {"block_h": 8, "block_w": 32, "us": 1.0}}, **bad_keys)))
    with pytest.warns(RuntimeWarning, match="corrupted tuning cache"):
        cache = tuning.TuningCache(str(path))
    assert len(cache) == 1
    assert cache.lookup(tuning.TuneKey(
        "pallas-interpret", "float32", "sobel5", "v2", 64, 64)) == (8, 32)


def test_cache_non_object_payload_skips_and_warns(tmp_path):
    path = tmp_path / "list.json"
    path.write_text("[1, 2, 3]")
    with pytest.warns(RuntimeWarning, match="expected a JSON object"):
        cache = tuning.TuningCache(str(path))
    assert len(cache) == 0


# One file of each schema before this one, each holding a tuning of the
# same workload in that schema's key layout (v1 files were written with no
# ``__meta__`` at first, then with ``{"version": 1}``).
_SLOT = "pallas-interpret/float32/sobel5/v2/reflect/gray/64x512"
_OLD_SCHEMA_FILES = {
    "no-meta": (None, "pallas-interpret/float32/5x5/v2/64x512"),
    "v1": (1, "pallas-interpret/float32/5x5/v2/64x512"),
    "v2": (2, "pallas-interpret/float32/5x5/v2/reflect/gray/64x512"),
    "v3": (3, _SLOT),
    "v4": (4, _SLOT + "/1/1x1x1"),
    "v5": (5, _SLOT + "/1/1x1x1/f32/0"),
    "v6": (6, _SLOT + "/1/1x1x1/f32/0/-"),
}


@pytest.mark.parametrize("schema", sorted(_OLD_SCHEMA_FILES))
def test_cache_of_another_schema_skips_and_warns(tmp_path, schema):
    """A file of an older schema is not migrated: its entries are dropped
    with a warning (their key layout is not this one's), the workload's
    lookup misses, and dispatch falls back to the default block shape."""
    version, key = _OLD_SCHEMA_FILES[schema]
    payload = {key: {"block_h": 16, "block_w": 128, "us": 12.5}}
    if version is not None:
        payload["__meta__"] = {"version": version}
    path = tmp_path / f"{schema}.json"
    path.write_text(json.dumps(payload))
    with pytest.warns(RuntimeWarning, match="is not the supported 7"):
        cache = tuning.TuningCache(str(path))
    assert len(cache) == 0
    assert cache.lookup(tuning.TuneKey(
        "pallas-interpret", "float32", "sobel5", "v2", 64, 512)) is None
    assert dispatch.choose_block_shape(
        64, 512, backend="pallas-interpret", cache=cache
    )[2] == "default"


def test_key_distinguishes_plan(tmp_path):
    """The same gradient operator tuned standalone vs inside a
    fused plan — slots must not collide, and two plans sharing a gradient
    stage keep separate slots (the plan identity hashes the full stage
    sequence, not just the name)."""
    from repro.core.filters import get_plan, make_plan, plan_identity

    cache = tuning.TuningCache(str(tmp_path / "c.json"))
    base = dict(backend="pallas-interpret", dtype="float32", operator="sobel5",
                variant="v2", h=128, w=256)
    canny = plan_identity(get_plan("canny5"))
    blur = plan_identity(get_plan("blur_sobel5"))
    assert canny != blur and canny.startswith("canny5.")
    cache.record(tuning.TuneKey(**base), 8, 32, 1.0)
    cache.record(tuning.TuneKey(**base, plan=canny), 16, 64, 2.0)
    cache.record(tuning.TuneKey(**base, plan=blur), 32, 128, 3.0)
    assert cache.lookup(tuning.TuneKey(**base)) == (8, 32)
    assert cache.lookup(tuning.TuneKey(**base, plan=canny)) == (16, 64)
    assert cache.lookup(tuning.TuneKey(**base, plan=blur)) == (32, 128)
    # a re-registered plan with different stages gets a different identity
    variant_plan = make_plan("canny5x", ("gaussian3", "sobel5", "nms"))
    assert plan_identity(variant_plan) != canny
    assert cache.lookup(
        tuning.TuneKey(**base, plan=plan_identity(variant_plan))) is None


def test_key_distinguishes_precision(tmp_path):
    """The same workload tuned per arithmetic lane — slots must not
    collide."""
    cache = tuning.TuningCache(str(tmp_path / "c.json"))
    base = dict(backend="pallas-interpret", dtype="uint8", operator="sobel5",
                variant="v2", h=128, w=256)
    cache.record(tuning.TuneKey(**base), 8, 32, 1.0)
    assert cache.lookup(tuning.TuneKey(**base, precision="int")) is None
    cache.record(tuning.TuneKey(**base, precision="int"), 16, 64, 2.0)
    assert cache.lookup(tuning.TuneKey(**base)) == (8, 32)
    assert cache.lookup(tuning.TuneKey(**base, precision="int")) == (16, 64)


def test_key_distinguishes_mesh(tmp_path):
    """Same workload, different device count / mesh shape -> different
    tuning slots (under spatial sharding the kernel tiles the
    halo-extended local block, so a 1x2x2 tuning must not collide with the
    single-device entry), and dispatch passes the mesh through."""
    cache = tuning.TuningCache(str(tmp_path / "c.json"))
    base = dict(backend="pallas-interpret", dtype="float32", operator="sobel5",
                variant="v2", h=128, w=256)
    cache.record(tuning.TuneKey(**base), 8, 32, 1.0)
    cache.record(tuning.TuneKey(**base, devices=4, mesh="1x2x2"), 16, 64, 2.0)
    cache.record(tuning.TuneKey(**base, devices=4, mesh="4x1x1"), 32, 128, 3.0)
    assert cache.lookup(tuning.TuneKey(**base)) == (8, 32)
    assert cache.lookup(tuning.TuneKey(**base, devices=4, mesh="1x2x2")) == (16, 64)
    assert cache.lookup(tuning.TuneKey(**base, devices=4, mesh="4x1x1")) == (32, 128)
    assert cache.lookup(tuning.TuneKey(**base, devices=8, mesh="2x2x2")) is None
    # choose_block_shape consults the mesh-specific slot...
    got = dispatch.choose_block_shape(
        128, 256, backend="pallas-interpret", cache=cache,
        devices=4, mesh="1x2x2",
    )
    assert got == (16, 64, "tuned")
    # ...and autotune records into it.
    bh, bw = tuning.autotune(24, 32, shapes=[(8, 16)], iters=1,
                                    cache=cache, save=False,
                                    devices=4, mesh="1x2x2")
    assert (bh, bw) == (8, 16)
    assert cache.lookup(
        tuning.TuneKey("pallas-interpret", "float32", "sobel5", "v2", 24, 32,
                       devices=4, mesh="1x2x2")
    ) == (8, 16)


def test_key_distinguishes_padding_and_layout(tmp_path):
    cache = tuning.TuningCache(str(tmp_path / "c.json"))
    base = dict(backend="pallas-interpret", dtype="uint8", operator="sobel5",
                variant="v2", h=128, w=256)
    cache.record(tuning.TuneKey(**base, padding="reflect", layout="gray"), 8, 32, 1.0)
    cache.record(tuning.TuneKey(**base, padding="zero", layout="rgb"), 16, 64, 2.0)
    assert cache.lookup(tuning.TuneKey(**base, padding="reflect", layout="gray")) == (8, 32)
    assert cache.lookup(tuning.TuneKey(**base, padding="zero", layout="rgb")) == (16, 64)
    assert cache.lookup(tuning.TuneKey(**base, padding="edge", layout="gray")) is None


# ---------------------------------------------------------------------------
# Backend dispatch
# ---------------------------------------------------------------------------

def test_resolve_backend():
    assert dispatch.resolve_backend("xla") == "xla"
    assert dispatch.resolve_backend("pallas-interpret") == "pallas-interpret"
    # auto on a CPU test host -> xla
    assert dispatch.resolve_backend(None) in ("xla", "pallas-tpu")
    with pytest.raises(ValueError):
        dispatch.resolve_backend("cuda")


def test_dispatch_xla_is_core(rng):
    img = _img(rng, (2, 33, 29))
    out = _dsobel(img, backend="xla")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(core_sobel(img)))


@pytest.mark.parametrize("variant", ["direct", "separable", "v1", "v2"])
def test_dispatch_backends_agree(variant, rng):
    img = _img(rng, (1, 45, 61))
    x = np.asarray(_dsobel(img, variant=variant, backend="xla"))
    p = np.asarray(
        _dsobel(img, variant=variant, backend="pallas-interpret",
                block_h=8, block_w=16)
    )
    np.testing.assert_array_equal(p, x)


def test_fig6_sweeps_both_dims():
    """fig6 must sweep block_h AND block_w through the tuner API."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks import fig6_blocksweep

    rows = fig6_blocksweep.run(smoke=True)
    hs = {r["name"].split("block_h=")[1].split("/")[0]
          for r in rows if "block_h=" in r["name"]}
    ws = {r["name"].split("block_w=")[1]
          for r in rows if "block_w=" in r["name"]}
    assert len(hs) > 1 and len(ws) > 1


def test_key_distinguishes_operator(tmp_path):
    """Same geometry, different operator -> different tuning slots (the
    schema-v3 point: scharr3/sobel7 tunings must not collide with sobel3/5)."""
    cache = tuning.TuningCache(str(tmp_path / "c.json"))
    base = dict(backend="pallas-interpret", dtype="float32", variant="separable",
                h=128, w=256)
    cache.record(tuning.TuneKey(operator="sobel3", **base), 8, 32, 1.0)
    cache.record(tuning.TuneKey(operator="scharr3", **base), 16, 64, 2.0)
    assert cache.lookup(tuning.TuneKey(operator="sobel3", **base)) == (8, 32)
    assert cache.lookup(tuning.TuneKey(operator="scharr3", **base)) == (16, 64)
    assert cache.lookup(tuning.TuneKey(operator="sobel7", **base)) is None


def test_autotune_operator_keyed(tmp_path):
    cache = tuning.TuningCache(str(tmp_path / "blocks.json"))
    bh, bw = tuning.autotune(24, 32, operator="scharr3", shapes=[(8, 16)],
                             iters=1, cache=cache, save=False)
    assert (bh, bw) == (8, 16)
    key = tuning.TuneKey("pallas-interpret", "float32", "scharr3", "separable", 24, 32)
    assert cache.lookup(key) == (8, 16)


def test_default_block_shape_folds_halo():
    """The satellite fix: ``size`` must actually constrain the default block
    — the halo'd (2r) working set has to fit the VMEM budget."""
    from repro.kernels.edge import default_block_shape
    from repro.kernels.tiling import tile_vmem_bytes

    # Roomy budget: size does not bite, defaults cap at (64, 256).
    assert default_block_shape(2048, 2048, 5) == (64, 256)
    # Tight budget: the block shrinks until the halo'd tile fits, and a
    # larger operator (bigger halo) can only shrink it further.
    budget = 96 * 1024
    shapes = {}
    for size in (3, 5, 7):
        bh, bw = default_block_shape(2048, 2048, size, max_vmem_bytes=budget)
        assert tile_vmem_bytes(bh, bw, size // 2) <= budget, (size, bh, bw)
        shapes[size] = bh * bw
    assert shapes[7] <= shapes[5] <= shapes[3]
    assert shapes[7] < 64 * 256
