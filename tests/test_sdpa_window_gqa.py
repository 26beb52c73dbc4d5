"""The attention op with a sliding ``window`` and grouped queries (kv
heads fewer than q heads) against a plain masked softmax written out
here: values and gradients, through XLA's chain (``_sdpa_reference``)
and through the blocked flash kernels in interpret mode, with the
window smaller than, equal to and larger than the sequence."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import ops
from paddle_tpu.ops.pallas import attention as A

OP = ops.get("scaled_dot_product_attention")
PATHS = {"xla": OP.fn, "blocked": OP.variants["pallas"]}


def plain(q, k, v, scale, window):
    """softmax over the keys row i may read (j <= i, and i - j < window
    where there is one), the kv heads repeated for their group."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    i = jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[2])[None, :]
    keep = j <= i
    if window:
        keep = keep & (i - j < window)
    w = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v,
                      precision=jax.lax.Precision.HIGHEST)


# (S, q heads, kv heads, window). 2048 with a window of 300 makes the
# band shorter than the grid's k axis (3 of 4 k-blocks, 5 of 8
# q-blocks): the clamped index maps and the skipped steps run. 8 over
# 1 is the benchmark cell's group (two q heads to a cell, four cells
# to a kv head).
CASES = [(2048, 4, 1, 300), (1024, 4, 2, 1024), (1024, 2, 2, 4096),
         (1024, 8, 1, 0), (1024, 4, 4, 700)]


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("s,h,hkv,window", CASES)
def test_values_and_gradients(path, s, h, hkv, window):
    r = np.random.RandomState(s + h + window)
    dh = 16
    q = jnp.asarray(r.randn(1, h, s, dh), jnp.float32)
    k = jnp.asarray(r.randn(1, hkv, s, dh), jnp.float32)
    v = jnp.asarray(r.randn(1, hkv, s, dh), jnp.float32)
    t = jnp.asarray(r.randn(1, h, s, dh), jnp.float32)
    scale = dh ** -0.5

    def op(q_, k_, v_):
        return PATHS[path](q_, k_, v_, None, scale=scale, causal=True,
                           window=window)

    want = plain(q, k, v, scale, window)
    got = jax.jit(op)(q, k, v)
    assert got.shape == want.shape
    # float32 on both sides: what is left is the order of the sums
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=1e-5)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * t)      # noqa: E731
    gw = jax.grad(loss(lambda *a: plain(*a, scale, window)),
                  (0, 1, 2))(q, k, v)
    gg = jax.jit(jax.grad(loss(op), (0, 1, 2)))(q, k, v)
    for a, b, name in zip(gg, gw, "qkv"):
        assert a.shape == b.shape, name     # dk, dv at the KV heads
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5,
                                   err_msg="d" + name)


def test_band_of_blocks():
    """Which k-blocks a q-block reads, by hand at the cell's tiles
    (256 x 512) and window (2048) over 8192 positions."""
    k_lo, k_hi, j_lo, j_hi = A._band(256, 512, 32, 16, True, 2048)
    # q-block 20 holds rows 5120..5375: keys 3073..5375, blocks 6..10
    assert (int(k_lo(20)), int(k_hi(20))) == (6, 10)
    assert (int(k_lo(0)), int(k_hi(0))) == (0, 0)
    # k-block 6 holds keys 3072..3583, read by rows 3072..5630
    assert (int(j_lo(6)), int(j_hi(6))) == (12, 21)
    assert A._n_steps(256, 512, 16, 2048) == 6      # the most any reads
    assert A._n_steps(512, 256, 32, 2048) == 11
    assert A._n_steps(256, 512, 16, 0) == 16        # full: every block
    for j in range(32):
        assert int(k_hi(j)) - int(k_lo(j)) + 1 <= 6
    for kk in range(16):
        assert int(j_hi(kk)) - int(j_lo(kk)) + 1 <= 11


def test_refusals_and_envelope():
    q = jnp.zeros((1, 6, 8, 4))
    kv = jnp.zeros((1, 4, 8, 4))
    with pytest.raises(ValueError, match="query heads"):
        OP.fn(q, kv, kv, None, causal=True)
    with pytest.raises(ValueError, match="causal"):
        OP.fn(q, q, q, None, window=4)
    # a site with no dropout takes the blocked kernels past the 1k
    # envelope only, in whole tiles
    assert A._blocked_applicable(8192, 8192)
    assert A._blocked_applicable(1024, 1024)
    assert not A._blocked_applicable(512, 512)
    assert not A._blocked_applicable(1000, 1000)
