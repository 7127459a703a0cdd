"""Optimizer + gradient-compression properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional [test] extra; module skips without it
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh, PartitionSpec as P

from repro.optim import adamw
from repro.optim.compress import compress_tree_psum, compressed_psum, init_error_state
from repro.optim.schedule import warmup_cosine


def test_adamw_converges_on_quadratic():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = adamw.init(params)
    for _ in range(300):
        grads = {"w": 2 * (params["w"] - target)}
        params, state, _ = adamw.update(grads, state, params, lr=jnp.float32(0.05), weight_decay=0.0)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target), atol=1e-2)


def test_grad_clipping():
    params = {"w": jnp.zeros(4)}
    state = adamw.init(params)
    grads = {"w": jnp.full(4, 1e6)}
    _, _, stats = adamw.update(grads, state, params, lr=jnp.float32(0.1), clip_norm=1.0)
    assert float(stats["grad_norm"]) > 1e5  # reported pre-clip norm


def test_schedule_shape():
    lrs = [float(warmup_cosine(s, peak_lr=1.0, warmup_steps=10, total_steps=100)) for s in range(100)]
    assert lrs[0] < lrs[9] <= 1.0
    assert abs(lrs[10] - 1.0) < 0.02
    assert lrs[-1] < 0.2
    assert all(l >= 0 for l in lrs)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 1000))
def test_compressed_psum_error_bound(seed):
    """Single-device axis: quantized psum error <= quantization step."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("d",))
    x = jax.random.normal(jax.random.key(seed), (64,), jnp.float32)

    f = jax.shard_map(
        lambda v: compressed_psum(v, "d", bits=8),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
    )
    out = np.asarray(f(x))
    step = float(jnp.max(jnp.abs(x))) / 127.0
    assert np.max(np.abs(out - np.asarray(x))) <= step * 0.5 + 1e-6


def test_error_feedback_telescopes():
    """Mean of compressed updates converges to mean of true grads."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("d",))
    g = {"w": jax.random.normal(jax.random.key(0), (32,), jnp.float32)}
    err = init_error_state(g)
    total = jnp.zeros(32)
    f = jax.shard_map(
        lambda gg, ee: compress_tree_psum(gg, ee, "d", bits=4),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        check_vma=False,
    )
    n = 50
    for _ in range(n):
        red, err = f(g, err)
        total = total + red["w"]
    np.testing.assert_allclose(np.asarray(total / n), np.asarray(g["w"]), atol=0.02)


def test_zero1_axes_add_data_dim():
    from repro.configs.base import ModelConfig
    from repro.models import Model
    from jax.sharding import AbstractMesh

    mesh = AbstractMesh((4, 2), ("data", "model"))
    cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=8,
                      num_heads=2, num_kv_heads=2, d_ff=16, vocab_size=32)
    m = Model(cfg)
    axes = adamw.opt_state_axes(m.logical_axes(), m.abstract_params(), mesh)
    flat = jax.tree.leaves(axes.mu, is_leaf=lambda x: isinstance(x, tuple))
    assert any("zero1" in t for t in flat if isinstance(t, tuple))
