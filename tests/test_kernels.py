"""Pallas kernel vs pure-jnp oracle: shape/dtype/block sweeps (interpret=True
on CPU; the kernel body is identical on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional [test] extra; module skips without it
from hypothesis import given, settings, strategies as st

from repro.core.filters import SobelParams
from repro.kernels import sobel_ref
from repro.kernels.edge import default_block_shape, edge_pallas, kernel_dtype


def _img(rng, shape, dtype=np.float32):
    x = rng.integers(0, 256, size=shape)
    return x.astype(dtype)


def ksobel(img, *, size=5, directions=0, variant="v2", params=None,
           block_h=None, block_w=None, **kw):
    """Raw-kernel magnitude with the old ops.sobel batch/default handling."""
    x = kernel_dtype(img)
    batch = x.shape[:-2]
    h, w = x.shape[-2], x.shape[-1]
    x = x.reshape((-1, h, w))
    dbh, dbw = default_block_shape(h, w, size)
    out = edge_pallas(
        x, operator=f"sobel{size}", variant=variant, params=params,
        directions=directions, block_h=block_h or dbh,
        block_w=block_w or dbw, interpret=True, **kw,
    )
    return out.reshape(batch + (h, w))


@pytest.mark.parametrize("variant", ["direct", "separable", "v1", "v2"])
@pytest.mark.parametrize(
    "shape,block_h",
    [((1, 64, 128), 16), ((2, 96, 73), 32),
     # the chip geometry at ragged and 1080p sizes (default 256-wide blocks)
     ((1, 237, 413), 64), ((1, 1080, 1920), 64)],
)
def test_kernel_matches_oracle(variant, shape, block_h, rng):
    img = jnp.asarray(_img(rng, shape))
    out = np.asarray(ksobel(img, variant=variant, block_h=block_h))
    ref = np.asarray(sobel_ref(img))
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-3)


@pytest.mark.parametrize("dtype", [np.uint8, np.int32, np.float32, jnp.bfloat16])
def test_kernel_dtype_sweep(dtype, rng):
    img = _img(rng, (1, 32, 64), np.float32)
    x = jnp.asarray(img).astype(dtype)
    out = np.asarray(ksobel(x, variant="v2", block_h=16))
    ref = np.asarray(sobel_ref(x.astype(jnp.float32)))
    tol = 2.0 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(out, ref, rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6, atol=tol)


@settings(max_examples=8, deadline=None)
@given(
    h=st.integers(8, 80),
    w=st.integers(8, 90),
    block_h=st.sampled_from([8, 16, 32, 64]),
    seed=st.integers(0, 2**16),
)
def test_kernel_shape_property(h, w, block_h, seed):
    rng = np.random.default_rng(seed)
    img = jnp.asarray(_img(rng, (1, h, w)))
    out = np.asarray(ksobel(img, variant="v2", block_h=block_h))
    ref = np.asarray(sobel_ref(img))
    assert out.shape == (1, h, w)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-3)


def test_kernel_block_invariance(rng):
    """Output must not depend on the BlockSpec tile height."""
    img = jnp.asarray(_img(rng, (1, 128, 96)))
    outs = [np.asarray(ksobel(img, variant="v2", block_h=bh)) for bh in (8, 16, 32, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


def test_kernel_3x3(rng):
    img = jnp.asarray(_img(rng, (2, 64, 64)))
    for d in (2, 4):
        out = np.asarray(ksobel(img, size=3, directions=d, variant="separable", block_h=16))
        ref = np.asarray(sobel_ref(img, size=3, directions=d))
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-3)


def test_kernel_components_output(rng):
    img = jnp.asarray(_img(rng, (1, 32, 48)))
    comps = edge_pallas(img, operator="sobel5", variant="v2",
                        out_components=True, block_h=16, block_w=48,
                        interpret=True)
    assert comps.shape == (1, 4, 32, 48)
    from repro.kernels.ref import sobel_components_ref

    refs = sobel_components_ref(jnp.asarray(img))
    for i, r in enumerate(refs):
        np.testing.assert_allclose(np.asarray(comps[:, i]), np.asarray(r), rtol=1e-6, atol=1e-3)


def test_kernel_generalized_params(rng):
    img = jnp.asarray(_img(rng, (1, 64, 64)))
    p = SobelParams(a=2.0, b=3.0, m=5.0, n=2.0)
    out = np.asarray(ksobel(img, variant="v2", params=p, block_h=32))
    ref = np.asarray(sobel_ref(img, params=p))
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-2)


# ---------------------------------------------------------------------------
# Fused selective-scan kernel (mamba-1 hot loop; see EXPERIMENTS.md §Perf)
# ---------------------------------------------------------------------------

def _naive_selective_scan(x, dt, bm, cm, a):
    B, L, DI = x.shape
    h = np.zeros((B, DI, a.shape[-1]))
    ys = []
    for t in range(L):
        da = np.exp(dt[:, t, :, None] * a)
        h = h * da + (dt[:, t] * x[:, t])[..., None] * bm[:, t, None, :]
        ys.append(np.einsum("bdn,bn->bd", h, cm[:, t]))
    return np.stack(ys, 1)


@pytest.mark.parametrize("chunk,block_d", [(8, 8), (16, 4), (32, 16)])
def test_selective_scan_kernel(chunk, block_d, rng):
    from repro.kernels.selective_scan import selective_scan

    B, L, DI, N = 2, 32, 16, 4
    x = rng.normal(0, 1, (B, L, DI)).astype(np.float32)
    dt = np.abs(rng.normal(0, 0.1, (B, L, DI))).astype(np.float32)
    bm = rng.normal(0, 1, (B, L, N)).astype(np.float32)
    cm = rng.normal(0, 1, (B, L, N)).astype(np.float32)
    a = -np.abs(rng.normal(1, 0.3, (DI, N))).astype(np.float32)
    out = np.asarray(
        selective_scan(*map(jnp.asarray, (x, dt, bm, cm, a)),
                       chunk=chunk, block_d=block_d, interpret=True)
    )
    np.testing.assert_allclose(out, _naive_selective_scan(x, dt, bm, cm, a),
                               rtol=3e-5, atol=3e-5)


def test_selective_scan_matches_mamba1_core(rng):
    """Kernel == the model's chunked associative-scan core on same inputs."""
    from repro.configs.base import ModelConfig
    from repro.kernels.selective_scan import selective_scan
    from repro.models import ssm
    from repro.models.layers import init_tree

    cfg = ModelConfig(name="m", family="ssm", num_layers=1, d_model=16,
                      vocab_size=7, ssm_type="mamba1", ssm_state=4, ssm_chunk=8,
                      ssm_dt_rank=4, attn_type="none", dtype="float32")
    params = init_tree(ssm.mamba1_params(cfg), jax.random.key(0))
    xin = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), jnp.float32)
    xc, z, dt, a, bm, cm, _, _ = ssm._mamba1_inputs(params, cfg, xin)
    y_kernel = selective_scan(
        xc.astype(jnp.float32), dt, bm, cm, a, chunk=8, block_d=8, interpret=True
    )
    # reproduce the model's scan output (pre gating/out-proj)
    ref = _naive_selective_scan(
        np.asarray(xc, np.float32), np.asarray(dt), np.asarray(bm), np.asarray(cm), np.asarray(a)
    )
    np.testing.assert_allclose(np.asarray(y_kernel), ref, rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# Fused flash-attention kernel (dense-train memory bottleneck; §Roofline)
# ---------------------------------------------------------------------------

def _dense_attn_ref(q, k, v, causal):
    S, T, D = q.shape[2], k.shape[2], q.shape[3]
    s = np.einsum("bhsd,bhtd->bhst", q, k) / np.sqrt(D)
    if causal:
        mask = np.arange(S)[:, None] >= np.arange(T)[None, :]
        s = np.where(mask, s, -1e30)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("bhst,bhtd->bhsd", w, v)


@pytest.mark.parametrize(
    "shape,blocks,causal",
    [
        ((2, 3, 16, 16, 8), (4, 4), True),
        ((1, 2, 32, 32, 16), (8, 16), True),
        ((2, 2, 8, 24, 8), (8, 8), False),
        ((1, 1, 64, 64, 4), (16, 32), True),
    ],
)
def test_flash_attention_kernel(shape, blocks, causal, rng):
    from repro.kernels.flash_attention import flash_attention

    B, H, S, T, D = shape
    bq, bkv = blocks
    q = rng.normal(0, 1, (B, H, S, D)).astype(np.float32)
    k = rng.normal(0, 1, (B, H, T, D)).astype(np.float32)
    v = rng.normal(0, 1, (B, H, T, D)).astype(np.float32)
    out = np.asarray(
        flash_attention(*map(jnp.asarray, (q, k, v)), causal=causal,
                        block_q=bq, block_kv=bkv, interpret=True)
    )
    np.testing.assert_allclose(out, _dense_attn_ref(q, k, v, causal), rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_model_core(rng):
    """Kernel == the model's dot_attention on identical GQA inputs."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models.attention import dot_attention

    B, KV, G, S, D = 2, 2, 2, 16, 8
    q5 = jnp.asarray(rng.normal(0, 1, (B, S, KV, G, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, S, KV, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, S, KV, D)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    ref = dot_attention(q5, k, v, pos_q=pos, pos_k=pos, causal=True, impl="dense")
    # fold (KV, G) -> H for the kernel; repeat kv heads per group
    qh = q5.transpose(0, 2, 3, 1, 4).reshape(B, KV * G, S, D)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
    out = flash_attention(qh, kh, vh, causal=True, block_q=8, block_kv=8, interpret=True)
    out = out.reshape(B, KV, G, S, D).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
