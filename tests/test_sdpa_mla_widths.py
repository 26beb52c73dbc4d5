"""The attention op with queries and keys of one width and values of
another (latent attention: 192-wide keys, 128 lanes of them a head's
own and 64 shared by every head, beside 128-wide values) against a
plain masked softmax written out here: values and gradients, through
XLA's chain (``_sdpa_reference``) and through the blocked flash
kernels in interpret mode; and the equal-width callers' lowering,
which the second width must not have changed."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import layers, ops, profiler
from paddle_tpu.ops.pallas import attention as A

OP = ops.get("scaled_dot_product_attention")
PATHS = {"xla": OP.fn, "blocked": OP.variants["pallas"]}


def plain(q, k, v, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    i = jnp.arange(q.shape[2])[:, None]
    j = jnp.arange(k.shape[2])[None, :]
    w = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v,
                      precision=jax.lax.Precision.HIGHEST)


# (S, heads, qk width, v width): the model's own pair, a toy pair with
# the wider values, and S past one k-block of 512 so that the online
# softmax and the backward walk several blocks
CASES = [(1024, 2, 192, 128), (1024, 3, 24, 16), (512, 2, 8, 16),
         (2048, 1, 48, 32)]


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("s,h,dqk,dv", CASES)
def test_values_and_gradients(path, s, h, dqk, dv):
    r = np.random.RandomState(s + h + dqk)
    q = jnp.asarray(r.randn(1, h, s, dqk), jnp.float32)
    k = jnp.asarray(r.randn(1, h, s, dqk), jnp.float32)
    v = jnp.asarray(r.randn(1, h, s, dv), jnp.float32)
    t = jnp.asarray(r.randn(1, h, s, dv), jnp.float32)
    scale = dqk ** -0.5

    def op(q_, k_, v_):
        return PATHS[path](q_, k_, v_, None, scale=scale, causal=True)

    before = profiler.counter_values().get(
        "sdpa_lowering.flash_blocked_mla", 0.0)
    want = plain(q, k, v, scale)
    got = jax.jit(op)(q, k, v)
    assert got.shape == want.shape == (1, h, s, dv)
    np.testing.assert_allclose(got, want, rtol=5e-5, atol=1e-5)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * t)      # noqa: E731
    gw = jax.grad(loss(lambda *a: plain(*a, scale)), (0, 1, 2))(q, k, v)
    gg = jax.jit(jax.grad(loss(op), (0, 1, 2)))(q, k, v)
    for a, b, name in zip(gg, gw, "qkv"):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5,
                                   err_msg="d" + name)
    after = profiler.counter_values().get(
        "sdpa_lowering.flash_blocked_mla", 0.0)
    # the two widths never reach the single-k-block pair, whose cells
    # are lanes of ONE width
    assert (after > before) == (path == "blocked")


def _traced(dqk, dv):
    x = lambda d: jax.ShapeDtypeStruct((1, 2, 1024, d),   # noqa: E731
                                       jnp.float32)
    seed = jnp.zeros((2,), jnp.float32)

    def site(q, k, v, g):
        out, pull = jax.vjp(
            lambda a, b, c: A._sdpa_flash(a, b, c, None, seed,
                                          0.25, 0.0, True, 0), q, k, v)
        return out, pull(g)
    return str(jax.make_jaxpr(site)(x(dqk), x(dqk), x(dv), x(dv)))


def test_equal_widths_lower_as_before():
    """A caller with one width gets the kernels it always got: the
    second width is read off v's own shape, so at equal widths every
    block, scratch shape and index map is what it was. At 48 / 32 the
    same kernels differ from the equal-width trace in numbers
    alone: no pad, slice or concatenate of a split head joins them
    (the traces hold the same primitives in the same order). Two
    kernels: the forward and the one backward (K, V, dK and dV of a
    head fit the VMEM model at 1,024 keys)."""
    import re
    same, two = _traced(32, 32), _traced(48, 32)
    assert same.count("pallas_call") == two.count("pallas_call") == 2
    shapeless = lambda t: re.sub(r"\d+", "N", t)       # noqa: E731
    assert shapeless(same) == shapeless(two)
    assert A._blocked_name(jnp.zeros((1, 2, 8, 32)),
                           jnp.zeros((1, 2, 8, 32))) == "flash_blocked"
    assert A._blocked_name(jnp.zeros((1, 2, 8, 48)),
                           jnp.zeros((1, 2, 8, 32))) \
        == "flash_blocked_mla"


def test_layer_refuses_two_widths_in_rank_3():
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = layers.data("q", shape=[16, 48], dtype="float32")
        v = layers.data("v", shape=[16, 32], dtype="float32")
        with pytest.raises(Exception, match="rank 4"):
            layers.scaled_dot_product_attention(q, q, v, causal=True,
                                                num_heads=2)
