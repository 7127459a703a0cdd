"""Whole runs with the timed path broken underneath: ``correct`` has to
come out false for each fault a cell can have. The look for a chip is
skipped and the frames are tiny; everything else is the run as it is."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import run as bench_run
from repro.kernels import dispatch

from _tiny import tiny


def _run(workload, seconds=0.5):
    return bench_run.run(
        ["--workload", workload, "--seed", str(2**31 + 99), "--seconds",
         str(seconds), "--trace", "0"],
        require_tpu=False, overrides=tiny(workload))


def _altered(res):
    """One answer changed where it is produced: a pixel of every frame's
    magnitude, and of its edge map where there is one."""
    out = dict(magnitude=res.magnitude.at[..., 0, 0].add(1.0))
    if res.edges is not None:
        out["edges"] = res.edges.at[..., 0, 0].set(~res.edges[..., 0, 0])
    return dataclasses.replace(res, **out)


def _half(res):
    """Half of the batch left out: its frames get the other half's answers."""
    def fold(a):
        if a is None or a.ndim == 0:
            return a
        k = max(1, a.shape[0] // 2)
        return jnp.concatenate([a[:k]] * (-(-a.shape[0] // k)))[:a.shape[0]]
    return jax.tree.map(fold, res)


def _half_rows(res):
    """Half of each frame left out, as a kernel grid that skips half its
    row blocks: the bottom half gets the top half's answers. (A request
    of the image cell is one frame, so it has no half batch to leave out.)"""
    def fold(a):
        if a is None or a.ndim < 2:
            return a
        k = max(1, a.shape[-2] // 2)
        top = a[..., :k, :]
        return jnp.concatenate([top] * (-(-a.shape[-2] // k)),
                               axis=-2)[..., :a.shape[-2], :]
    return jax.tree.map(fold, res)


def _nan(res):
    """Every served magnitude is NaN."""
    return dataclasses.replace(res, magnitude=jnp.full_like(res.magnitude,
                                                            jnp.nan))


def _image(fault):
    real = dispatch.edge

    def edge(images, config, **kw):
        return fault(real(images, config, **kw))
    return edge


def _stream(fault):
    real = dispatch.edge_stream

    def edge_stream(images, config, state=None, **kw):
        res, new = real(images, config, state, **kw)
        return fault(res), new
    return edge_stream


@pytest.mark.parametrize("fault", [_altered, _half_rows, _nan],
                         ids=["altered", "half-rows", "nan"])
def test_image_faults(monkeypatch, fault):
    monkeypatch.setattr(dispatch, "edge", _image(fault))
    res = _run("hd-batch-mag")
    assert res["correct"] is False
    err = res["checks"]["mag_err"]
    assert err["value"] == "inf" if fault is _nan else err["value"] > err["limit"]


@pytest.mark.parametrize("workload", ["cam1080-moving", "cam1080-noisy"])
@pytest.mark.parametrize("fault", [_altered, _half, _nan],
                         ids=["altered", "half", "nan"])
def test_stream_faults(monkeypatch, workload, fault):
    monkeypatch.setattr(dispatch, "edge_stream", _stream(fault))
    res = _run(workload)
    assert res["correct"] is False
    if fault is _nan:
        assert res["checks"]["mag_err"]["value"] == "inf"


@pytest.mark.parametrize("workload", ["hd-batch-mag", "cam1080-moving"])
def test_a_window_on_the_fallback_gives_no_result(monkeypatch, workload):
    """The configured Pallas path fails, so the guard serves on its XLA
    twin: the run refuses to report the twin's timings as the program's."""
    name = "edge" if workload == "hd-batch-mag" else "edge_stream"
    real = getattr(dispatch, name)

    def broken(images, config, *args, **kw):
        if config.backend != "xla":
            raise RuntimeError("the kernel does not compile")
        return real(images, config, *args, **kw)

    monkeypatch.setattr(dispatch, name, broken)
    with pytest.raises(bench_run.RunError, match="fell back"):
        bench_run.run(
            ["--workload", workload, "--seed", "3", "--seconds", "0.3",
             "--trace", "0"], require_tpu=False,
            overrides=tiny(workload, "pallas-interpret"))


def test_stream_step_keeps_stale_maps(monkeypatch):
    """The step moves the cached frame on but returns the cached maps
    unchanged: tiles the delta test skips splice stale values."""
    real = dispatch.edge_stream

    def edge_stream(images, config, state=None, **kw):
        res, new = real(images, config, state, **kw)
        old = state if state is not None else new
        if state is None or not state.initialized:
            return res, new
        return res, dataclasses.replace(new, primary=old.primary, bmax=old.bmax)

    monkeypatch.setattr(dispatch, "edge_stream", edge_stream)
    assert _run("cam1080-moving")["correct"] is False


def test_stream_state_returned_whole_is_harmless(monkeypatch):
    """A step that returns its state unchanged leaves the state cold, so
    every frame recomputes every tile: the answers stay right and only the
    skip share shows it. No such fault can make a wrong answer here."""
    real = dispatch.edge_stream

    def edge_stream(images, config, state=None, **kw):
        res, new = real(images, config, state, **kw)
        if state is None:
            from repro.api import StreamState
            h, w = images.shape[-2:]
            state = StreamState.init(images.shape[0], h, w, config,
                                     dtype=images.dtype)
        return res, state

    monkeypatch.setattr(dispatch, "edge_stream", edge_stream)
    res = bench_run.run(
        ["--workload", "cam1080-moving", "--seed", "5", "--seconds", "0.3",
         "--trace", "1"], require_tpu=False,
        overrides=tiny("cam1080-moving"))
    assert res["correct"] is True
    assert res["metrics"]["tile_skip_share"]["value"] == 0.0
