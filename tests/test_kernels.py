"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes, dtypes, and kernel options."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.kernels import fixed_order, ops, ref

KEY = jax.random.PRNGKey(0)


# ----------------------------------------------------------------------
# gp_gram
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["exp", "rbf"])
@pytest.mark.parametrize("m,n,d", [
    (1, 1, 1), (7, 5, 3), (10, 10, 11), (40, 40, 41),
    (128, 128, 128), (130, 60, 17),
])
def test_gram_matches_ref(kind, m, n, d):
    k1, k2 = jax.random.split(KEY)
    xa = jax.random.normal(k1, (m, d), jnp.float32)
    xb = jax.random.normal(k2, (n, d), jnp.float32)
    got = ops.gram(xa, xb, 0.7, 1.3, kind=kind, impl="pallas")
    want = ref.gram(xa, xb, 0.7, 1.3, kind=kind)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("kind", ["exp", "rbf"])
def test_gram_grad_pallas_matches_jnp(kind):
    """jax.grad through the Pallas Gram (its custom VJP) equals jax.grad
    through the jnp reference, w.r.t. both pattern sets and both
    hyper-parameters — the GP evidence loss needs exactly this."""
    k1, k2 = jax.random.split(KEY)
    xa = jax.random.normal(k1, (10, 11), jnp.float32)
    xb = jax.random.normal(k2, (7, 11), jnp.float32)
    w = jax.random.normal(KEY, (10, 7), jnp.float32)

    def loss(impl):
        return lambda a, b, ell, sf: (
            w * ops.gram(a, b, ell, sf, kind=kind, impl=impl)).sum()

    args = (xa, xb, jnp.float32(0.7), jnp.float32(1.3))
    got = jax.grad(loss("pallas"), argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(loss("jnp"), argnums=(0, 1, 2, 3))(*args)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("n", [1, 2, 3, 10, 11, 24])
def test_tree_sum_is_a_fixed_pairwise_tree(n):
    """tree_sum adds halves until one remains, padding an odd length
    with an exact zero, along any axis: the same f32 adds as this loop."""
    x = np.asarray(jax.random.normal(KEY, (3, n)), np.float32)

    def pairwise(v):
        v = list(v)
        while len(v) > 1:
            if len(v) % 2:
                v.append(np.float32(0.0))
            h = len(v) // 2
            v = [a + b for a, b in zip(v[:h], v[h:])]
        return v[0]

    want = np.asarray([pairwise(r) for r in x], np.float32)
    np.testing.assert_array_equal(fixed_order.tree_sum(x, 1), want)
    np.testing.assert_array_equal(fixed_order.tree_sum(x.T, 0), want)


@pytest.mark.parametrize("kind", ["exp", "rbf"])
def test_gram_properties(kind):
    x = jax.random.normal(KEY, (12, 5), jnp.float32)
    K = np.asarray(ops.gram(x, x, 1.0, 2.0, kind=kind, impl="pallas"))
    np.testing.assert_allclose(K, K.T, atol=1e-5)          # symmetry
    # diag = sf^2 up to fp32 cancellation in the matmul distance identity
    np.testing.assert_allclose(np.diag(K), 4.0, rtol=3e-3)
    assert (K > 0).all() and (K <= 4.0 + 1e-4).all()


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 33), n=st.integers(1, 33), d=st.integers(1, 20),
       ell=st.floats(0.1, 5.0), sf=st.floats(0.1, 3.0))
def test_gram_hypothesis(m, n, d, ell, sf):
    k1, k2 = jax.random.split(KEY)
    xa = jax.random.normal(k1, (m, d), jnp.float32)
    xb = jax.random.normal(k2, (n, d), jnp.float32)
    got = ops.gram(xa, xb, ell, sf, kind="exp", impl="pallas")
    want = ref.gram(xa, xb, ell, sf, kind="exp")
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-6)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

def _qkv(b, hq, hkv, s, t, d, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (b, hq, s, d), dtype)
    k = jax.random.normal(k2, (b, hkv, t, d), dtype)
    v = jax.random.normal(k3, (b, hkv, t, d), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 1, 1, 32, 16), (2, 4, 2, 64, 32), (1, 8, 1, 128, 64),
])
def test_flash_causal(dtype, tol, b, hq, hkv, s, d):
    q, k, v = _qkv(b, hq, hkv, s, s, d, dtype)
    got = ops.attention(q, k, v, causal=True, impl="pallas", bq=32, bk=32)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.float32(got), np.float32(want),
                               rtol=tol, atol=tol)


def test_flash_padded_shapes():
    """Non-multiple S/T and odd head dims exercise the padding path."""
    q, k, v = _qkv(2, 4, 4, 48, 48, 24, jnp.float32)
    got = ops.attention(q, k, v, causal=True, impl="pallas", bq=32, bk=32)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_flash_decode_prefix():
    """q shorter than kv (decode-style suffix alignment)."""
    q, k, v = _qkv(1, 4, 2, 32, 128, 32, jnp.float32)
    got = ops.attention(q, k, v, causal=True, impl="pallas", bq=32, bk=32)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_flash_noncausal():
    q, k, v = _qkv(1, 2, 2, 64, 64, 32, jnp.float32)
    got = ops.attention(q, k, v, causal=False, impl="pallas", bq=32, bk=32)
    want = ref.attention(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


@settings(max_examples=10, deadline=None)
@given(s=st.sampled_from([16, 32, 64]), group=st.sampled_from([1, 2, 4]),
       d=st.sampled_from([16, 32]))
def test_flash_hypothesis(s, group, d):
    q, k, v = _qkv(1, 4, 4 // group, s, s, d, jnp.float32)
    got = ops.attention(q, k, v, causal=True, impl="pallas", bq=16, bk=16)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=5e-5)


def test_ref_attention_softmax_rows_sum_to_one():
    q, k, v = _qkv(1, 2, 2, 16, 16, 8, jnp.float32)
    ones = jnp.ones_like(v)
    out = ref.attention(q, k, ones, causal=True)
    np.testing.assert_allclose(out, 1.0, rtol=1e-5)
