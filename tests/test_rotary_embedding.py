"""``rotary_embedding`` (ops/nn_ops.py, layers.rotary_embedding): a part
of the head beside plain lanes, and the interleaved pair layout,
against numpy in float64; the gradient (a rotation's transpose is the
rotation back); the interleaved form as the half form under the lane
permutation; the type kept; the lowering counted by its path; the
default (whole head, rotate-half) lowering as it did before the op
took a part or a layout."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, profiler
from paddle_tpu.ops import nn_ops

THETA = 1000.0


def _numpy(x, start, width, interleaved, sign=1.0, theta=THETA):
    """float64; ``sign`` -1 turns back."""
    x = np.asarray(x, np.float64)
    ang = sign * np.arange(x.shape[-2])[:, None] \
        * theta ** (-2.0 * np.arange(width // 2) / width)[None, :]
    part = x[..., start:start + width]
    a, b = (part[..., 0::2], part[..., 1::2]) if interleaved \
        else (part[..., :width // 2], part[..., width // 2:])
    ra, rb = a * np.cos(ang) - b * np.sin(ang), \
        b * np.cos(ang) + a * np.sin(ang)
    out, turned = x.copy(), np.empty_like(part)
    if interleaved:
        turned[..., 0::2], turned[..., 1::2] = ra, rb
    else:
        turned[..., :width // 2], turned[..., width // 2:] = ra, rb
    out[..., start:start + width] = turned
    return out


def _op(x, start, width, interleaved):
    return nn_ops.rotary_embedding(
        x, theta=THETA, start=start,
        width=0 if start + width == x.shape[-1] else width,
        interleaved=interleaved)


CASES = [(0, 24, False), (0, 24, True), (16, 8, True), (16, 8, False),
         (4, 12, True), (0, 8, False)]


@pytest.mark.parametrize("start,width,interleaved", CASES)
def test_against_numpy_with_its_gradient(start, width, interleaved):
    rs = np.random.RandomState(start + width)
    x = rs.randn(2, 3, 17, 24).astype(np.float32)
    g = rs.randn(2, 3, 17, 24).astype(np.float32)
    got, pull = jax.vjp(lambda t: _op(t, start, width, interleaved),
                        jnp.asarray(x))
    np.testing.assert_allclose(got, _numpy(x, start, width, interleaved),
                               atol=2e-6)
    # lanes outside the part pass through untouched, bit for bit
    keep = np.ones(24, bool)
    keep[start:start + width] = False
    assert np.array_equal(np.asarray(got)[..., keep], x[..., keep])
    dx, = pull(jnp.asarray(g))
    np.testing.assert_allclose(
        dx, _numpy(g, start, width, interleaved, sign=-1.0), atol=2e-6)


@pytest.mark.parametrize("start,width", [(0, 16), (8, 8), (2, 12)])
def test_interleaved_is_the_half_form_under_the_lane_permutation(
        start, width):
    """Lane 2i of the part to lane i, lane 2i + 1 to i + width/2 (what
    the public DeepSeek-V3 code does before it turns): turned in the
    half layout and moved back, that is the interleaved turn in
    place."""
    x = jnp.asarray(np.random.RandomState(5).randn(1, 2, 9, 16),
                    jnp.float32)
    lanes = np.arange(16)
    part = lanes[start:start + width]
    to_half = lanes.copy()
    to_half[start:start + width] = np.concatenate([part[0::2],
                                                   part[1::2]])
    back = np.argsort(to_half)
    half = _op(x[..., to_half], start, width, False)[..., back]
    np.testing.assert_allclose(_op(x, start, width, True), half,
                               atol=1e-6)


def test_the_type_is_kept_and_the_angles_are_float32():
    """bf16 in, bf16 out; the turn itself in float32: at position 8000
    a bf16 angle would be off by whole radians."""
    x = np.random.RandomState(2).randn(1, 1, 8192, 8).astype(np.float32)
    xb = jnp.asarray(x, jnp.bfloat16)
    got = _op(xb, 4, 4, True)
    assert got.dtype == jnp.bfloat16
    want = _numpy(np.asarray(xb.astype(jnp.float32)), 4, 4, True)
    # float32 angles: 8191 x 1 is exact, its cosine good to 1e-6;
    # what is left is the output's own rounding to bf16
    np.testing.assert_allclose(got.astype(jnp.float32)[0, 0, -64:],
                               want[0, 0, -64:], atol=0.02, rtol=0.01)


def test_each_lowering_is_counted_by_its_path():
    x = jnp.zeros((1, 2, 4, 8), jnp.float32)
    names = ["rotary_lowering." + p for p in nn_ops._ROTARY_PATHS]
    before = {n: profiler.counter_values().get(n, 0.0) for n in names}
    _op(x, 0, 8, False)
    _op(x, 4, 4, True)
    _op(x, 4, 4, True)
    after = profiler.counter_values()
    moved = {n.split(".")[1]: after[n] - before[n] for n in names}
    assert moved == {"whole_half": 1.0, "whole_interleaved": 0.0,
                     "partial_half": 0.0, "partial_interleaved": 2.0}
    with pytest.raises(ValueError, match=r"lanes \[4, 10\) of 8"):
        nn_ops.rotary_embedding(x, start=4, width=6)


def test_the_default_lowers_as_it_did():
    """The sliding layers of ``models/afmoe.py`` call the op with its
    defaults: the whole head, rotate-half, two slices and one
    concatenation -- no product with a permutation."""
    x = jax.ShapeDtypeStruct((1, 4, 64, 128), jnp.bfloat16)
    text = jax.jit(lambda t: nn_ops.rotary_embedding(
        t, theta=1e4)).lower(x).as_text()
    assert text.count("stablehlo.slice") == 2
    assert text.count("stablehlo.concatenate") == 1
    assert "stablehlo.dot_general" not in text
    part = jax.jit(lambda t: nn_ops.rotary_embedding(
        t, theta=1e4, start=64, width=64, interleaved=True)).lower(
            x).as_text()
    assert part.count("stablehlo.dot_general") == 1


def test_the_layer_hands_the_part_and_the_layout_to_the_op():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        x = layers.data("x", shape=[2, 6, 8], dtype="float32")
        layers.rotary_embedding(x, theta=50.0)
        y = layers.rotary_embedding(x, theta=50.0, start=4, width=4,
                                    interleaved=True)
    plain, part = [op.attrs for op in main.global_block().ops
                   if op.type == "rotary_embedding"]
    assert (plain["start"], plain["width"], plain["interleaved"]) \
        == (0, 0, False)
    assert (part["start"], part["width"], part["interleaved"]) \
        == (4, 4, True)
    data = np.random.RandomState(1).randn(3, 2, 6, 8).astype(np.float32)
    got, = fluid.Executor().run(main, feed={"x": data}, fetch_list=[y])
    np.testing.assert_allclose(got, _numpy(data, 4, 4, True, theta=50.0),
                               atol=2e-6)
