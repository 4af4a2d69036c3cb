"""Per-kernel correctness: interpret-mode Pallas vs ref.py oracle,
swept over shapes and dtypes (assignment deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_causal, ops, ref
from repro.kernels.attention import flash_attention

KEY = jax.random.PRNGKey(7)


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

ATTN_CASES = [
    # b, hq, hkv, tq, tk, d, causal, window, softcap, q_off, kv_off
    (2, 4, 2, 128, 128, 64, True, None, None, 0, 0),
    (1, 8, 4, 256, 256, 128, True, 64, None, 0, 0),
    (1, 2, 2, 100, 100, 32, True, None, 50.0, 0, 0),
    (2, 4, 1, 1, 320, 64, True, None, None, 319, 0),     # decode
    (1, 4, 4, 1, 64, 32, True, 64, None, 100, 37),       # rolling decode
    (1, 4, 4, 128, 256, 64, False, None, None, 0, 0),    # encoder
    (1, 2, 1, 96, 96, 16, True, 32, 30.0, 0, 0),         # all features
]


@pytest.mark.parametrize(
    "b,hq,hkv,tq,tk,d,causal,window,softcap,qoff,kvoff", ATTN_CASES)
def test_flash_attention_vs_ref(b, hq, hkv, tq, tk, d, causal, window,
                                softcap, qoff, kvoff):
    ks = jax.random.split(jax.random.PRNGKey(b * 31 + tq), 3)
    q = _rand(ks[0], (b, hq, tq, d))
    k = _rand(ks[1], (b, hkv, tk, d))
    v = _rand(ks[2], (b, hkv, tk, d))
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, q_offset=qoff,
                          kv_offset=kvoff, block_q=64, block_k=64,
                          interpret=True)
    want = ref.mha(q, k, v, causal=causal, window=window,
                   softcap=softcap, q_offset=qoff, kv_offset=kvoff)
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (1, 4, 64, 32), dtype)
    k = _rand(ks[1], (1, 2, 64, 32), dtype)
    v = _rand(ks[2], (1, 2, 64, 32), dtype)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    want = ref.mha(q, k, v)
    assert out.dtype == dtype
    tol = 1e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol,
                               rtol=tol)


def test_xla_paths_match_ref():
    """Blocked XLA paths (qchunk + two-block SWA) match the oracle."""
    ks = jax.random.split(KEY, 3)
    for win in (None, 128):
        q = _rand(ks[0], (1, 4, 1024, 64))
        k = _rand(ks[1], (1, 2, 1024, 64))
        v = _rand(ks[2], (1, 2, 1024, 64))
        out = ops.attention(q, k, v, causal=True, window=win, impl="xla",
                            block_q=128)
        want = ref.mha(q, k, v, causal=True, window=win)
        np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


def test_attention_grad_custom_vjp():
    """The blocked XLA path is differentiable."""
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (1, 2, 32, 16))

    def f_xla(x):
        return ops.attention(x, x, x, impl="xla").sum()

    g = jax.grad(f_xla)(q)
    assert g.shape == q.shape and bool(jnp.isfinite(g).all())


# ----------------------------------------------------------------------
# causal flash attention (forward and backward kernels)
# ----------------------------------------------------------------------

FLASH_SHAPES = {
    # hq, hkv, q/k width, v width
    "mla": (16, 16, 192, 128),
    "gqa": (16, 8, 128, 128),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_causal_flash_vs_ref(shape, dtype):
    """Output and the gradients of q, k and v against the oracle, at
    three 128-row tiles: tiles above the diagonal skipped, the diagonal
    masked, the rest accumulated."""
    hq, hkv, dqk, dv = FLASH_SHAPES[shape]
    b, t = 2, 384
    ks = jax.random.split(jax.random.PRNGKey(hq + dqk), 4)
    q = _rand(ks[0], (b, hq, t, dqk), dtype)
    k = _rand(ks[1], (b, hkv, t, dqk), dtype)
    v = _rand(ks[2], (b, hkv, t, dv), dtype)
    w = _rand(ks[3], (b, hq, t, dv))
    sc = dqk ** -0.5

    def flash(q_, k_, v_):
        return flash_causal.causal_attention(q_, k_, v_, scale=sc,
                                             block=128, interpret=True)

    def oracle(q_, k_, v_):
        return ref.mha(q_.astype(jnp.float32), k_.astype(jnp.float32),
                       v_.astype(jnp.float32), causal=True, scale=sc)

    def loss(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w)

    out = flash(q, k, v)
    assert out.shape == (b, hq, t, dv) and out.dtype == dtype
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(oracle), (0, 1, 2))(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for a, e in zip((out,) + got, (oracle(q, k, v),) + want):
        assert a.dtype == dtype
        err = jnp.max(jnp.abs(a.astype(jnp.float32) - e)) / jnp.max(
            jnp.abs(e))
        assert float(err) < tol


FULL = dict(causal=True, window=None, softcap=None, q_offset=0,
            kv_offset=0)


@pytest.mark.parametrize("tq, tk, flags, impl, backend, want", [
    (8192, 8192, {}, "auto", "tpu", 512),
    (2048, 2048, {}, "auto", "tpu", 512),
    (384, 384, {}, "auto", "tpu", 128),
    (2048, 2048, {}, "auto", "cpu", None),
    (2048, 2048, {}, "xla", "tpu", None),
    (2048, 2048, {}, "pallas", "cpu", 512),
    (2048, 2048, {}, "interpret", "cpu", 512),
    (2048, 2048, {"window": 512}, "auto", "tpu", None),
    (2048, 2048, {"softcap": 50.0}, "auto", "tpu", None),
    (2048, 2048, {"q_offset": 128}, "auto", "tpu", None),
    (2048, 2048, {"kv_offset": -5}, "auto", "tpu", None),
    (2048, 2048, {"q_offset": np.int32(0)}, "auto", "tpu", None),
    (2048, 2048, {"causal": False}, "auto", "tpu", None),
    (1, 2048, {"q_offset": 2047}, "auto", "tpu", None),      # decode
    (100, 100, {}, "auto", "tpu", None),                     # no tile
    (2048, 2048, {"devices": 4}, "auto", "tpu", None),       # a mesh
], ids=["moonlight", "qwen3", "small", "cpu", "xla", "pallas",
        "interpret", "window", "softcap", "q_offset", "kv_offset",
        "traced_offset", "encoder", "decode", "ragged", "mesh"])
def test_flash_dispatch(tq, tk, flags, impl, backend, want):
    got = ops.flash_block(tq, tk, 192, 128, **dict(FULL, **flags),
                          impl=impl, backend=backend)
    assert got == want


def test_auto_attention_on_cpu_is_the_blocked_path():
    """On the CPU ``impl="auto"`` computes exactly what "xla" does."""
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (1, 4, 256, 64), jnp.bfloat16)
    k = _rand(ks[1], (1, 2, 256, 64), jnp.bfloat16)
    v = _rand(ks[2], (1, 2, 256, 64), jnp.bfloat16)
    for kw in (dict(causal=True), dict(causal=True, window=64),
               dict(causal=True, softcap=30.0)):
        np.testing.assert_array_equal(
            ops.attention(q, k, v, **kw, block_q=64),
            ops.attention(q, k, v, **kw, impl="xla", block_q=64))


def test_interpret_attention_takes_the_flash_kernels():
    """``impl="interpret"`` runs the causal flash kernels at the rule's
    tile where the shapes allow, with the default 1/sqrt(d) scale."""
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (1, 2, 128, 64))
    k = _rand(ks[1], (1, 1, 128, 64))
    v = _rand(ks[2], (1, 1, 128, 32))
    out = ops.attention(q, k, v, impl="interpret")
    np.testing.assert_allclose(out, ref.mha(q, k, v), atol=3e-5, rtol=3e-5)
    np.testing.assert_array_equal(out, flash_causal.causal_attention(
        q, k, v, scale=64 ** -0.5, block=128, interpret=True))


# ----------------------------------------------------------------------
# RG-LRU
# ----------------------------------------------------------------------

@pytest.mark.parametrize("b,t,d,bt,bd", [
    (2, 128, 64, 32, 64), (1, 300, 100, 64, 32), (3, 64, 512, 16, 128),
    (1, 17, 9, 8, 8),
])
def test_rglru_vs_ref(b, t, d, bt, bd):
    ks = jax.random.split(jax.random.PRNGKey(t), 4)
    x = _rand(ks[0], (b, t, d))
    a = jax.nn.sigmoid(_rand(ks[1], (b, t, d))) * 0.98
    g = jax.nn.sigmoid(_rand(ks[2], (b, t, d)))
    h0 = _rand(ks[3], (b, d))
    yr, hr = ref.rglru(x, a, g, h0)
    yi, hi = ops.rglru(x, a, g, h0, impl="interpret", block_t=bt,
                       block_d=bd)
    yx, hx = ops.rglru(x, a, g, h0, impl="xla")
    np.testing.assert_allclose(yi, yr, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(hi, hr, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(yx, yr, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(hx, hr, atol=2e-5, rtol=2e-5)


def test_rglru_xla_grad():
    ks = jax.random.split(KEY, 3)
    x = _rand(ks[0], (2, 32, 16))
    a = jax.nn.sigmoid(_rand(ks[1], (2, 32, 16))) * 0.9
    g = jax.nn.sigmoid(_rand(ks[2], (2, 32, 16)))
    grad = jax.grad(lambda x_: ops.rglru(x_, a, g, impl="xla")[0].sum())(x)
    assert bool(jnp.isfinite(grad).all())


# ----------------------------------------------------------------------
# FedAvg reduction
# ----------------------------------------------------------------------

@pytest.mark.parametrize("n,d,bd", [
    (10, 5000, 512), (37, 1234, 256), (100, 65536, 2048), (3, 8, 8),
])
def test_fedavg_vs_ref(n, d, bd):
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    u = _rand(ks[0], (n, d))
    w = jax.random.uniform(ks[1], (n,)) * 10
    m = (jax.random.uniform(ks[2], (n,)) > 0.3).astype(jnp.float32)
    if not m.any():
        m = m.at[0].set(1.0)
    want = ref.fedavg_reduce(u, w, m)
    got = ops.fedavg(u, w, m, impl="interpret", block_d=bd)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_fedavg_single_active():
    u = jnp.stack([jnp.full((64,), 3.0), jnp.full((64,), 9.0)])
    w = jnp.ones(2)
    m = jnp.array([0.0, 1.0])
    out = ops.fedavg(u, w, m, impl="interpret", block_d=64)
    np.testing.assert_allclose(out, jnp.full((64,), 9.0), atol=1e-6)


# ----------------------------------------------------------------------
# Chunk quantization
# ----------------------------------------------------------------------

@pytest.mark.parametrize("n,e", [(7, 512 * 128), (1, 128), (16, 1024)])
def test_quantize_vs_ref(n, e):
    x = _rand(jax.random.PRNGKey(e), (n, e)) * 5
    q1, s1 = ref.chunk_quantize(x)
    q2, s2 = ops.quantize(x, impl="interpret")
    assert bool((q1 == q2).all())
    np.testing.assert_allclose(s1, s2, atol=1e-7)
    d2 = ops.dequantize(q2, s2, impl="interpret")
    rel = float(jnp.abs(d2 - x).max() / jnp.abs(x).max())
    assert rel < 0.01            # int8 symmetric: <1% of amax


def test_quantize_zero_chunk():
    x = jnp.zeros((2, 256))
    q, s = ops.quantize(x, impl="interpret")
    assert bool((q == 0).all())
    d = ops.dequantize(q, s, impl="interpret")
    assert bool((d == 0).all())
