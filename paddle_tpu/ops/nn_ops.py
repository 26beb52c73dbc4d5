"""Neural-net ops: activations, losses, conv/pool, normalization, embedding.

Reference: paddle/fluid/operators/{activation_op.cc, softmax_op.cc,
cross_entropy_op.cc, softmax_with_cross_entropy_op.cc, conv_op.cc
(+ conv_cudnn_op.cu.cc), pool_op.cc, batch_norm_op.cc, layer_norm_op.cc,
group_norm_op.cc, dropout_op.cc, lookup_table_op.cc, ...}.

TPU-native: convs lower to lax.conv_general_dilated (XLA tiles them onto
the MXU); normalizations are expressed in plain jnp so XLA fuses the
elementwise chains into surrounding matmuls; dropout uses counter-based
RNG threaded by the executor. Data layout follows the reference's NCHW
for API parity — XLA relayouts internally for the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.enforce import InvalidArgumentError
from .registry import register


# -- activations ------------------------------------------------------------

def _unary(name, fn):
    register(name, ["X"], ["Out"])(lambda x: fn(x))


_unary("relu", jax.nn.relu)
_unary("sigmoid", jax.nn.sigmoid)
_unary("tanh", jnp.tanh)
_unary("softplus", jax.nn.softplus)
_unary("softsign", jax.nn.soft_sign)
_unary("relu6", lambda x: jnp.clip(x, 0.0, 6.0))
_unary("logsigmoid", jax.nn.log_sigmoid)


@register("gelu", ["X"], ["Out"])
def gelu(x, *, approximate=True):
    return jax.nn.gelu(x, approximate=approximate)


@register("leaky_relu", ["X"], ["Out"])
def leaky_relu(x, *, alpha=0.02):
    return jnp.where(x >= 0, x, alpha * x)


@register("elu", ["X"], ["Out"])
def elu(x, *, alpha=1.0):
    return jax.nn.elu(x, alpha)


@register("selu", ["X"], ["Out"])
def selu(x, *, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * (jnp.exp(x) - 1.0))


@register("swish", ["X"], ["Out"])
def swish(x, *, beta=1.0):
    return x * jax.nn.sigmoid(beta * x)


@register("hard_sigmoid", ["X"], ["Out"])
def hard_sigmoid(x, *, slope=0.2, offset=0.5):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


@register("hard_swish", ["X"], ["Out"])
def hard_swish(x, *, threshold=6.0, scale=6.0, offset=3.0):
    return x * jnp.clip(x + offset, 0.0, threshold) / scale


@register("prelu", ["X", "Alpha"], ["Out"])
def prelu(x, alpha, *, mode="all"):
    if mode == "channel" and alpha.ndim == 1:
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return jnp.where(x >= 0, x, alpha * x)


@register("softmax", ["X"], ["Out"])
def softmax(x, *, axis=-1):
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax", ["X"], ["Out"])
def log_softmax(x, *, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


@register("maxout", ["X"], ["Out"])
def maxout(x, *, groups, axis=1):
    c = x.shape[axis]
    new_shape = (x.shape[:axis] + (c // groups, groups)
                 + x.shape[axis + 1:])
    return jnp.max(x.reshape(new_shape), axis=axis + 1)


# -- losses -----------------------------------------------------------------

@register("cross_entropy", ["X", "Label"], ["Y"], nondiff=("Label",))
def cross_entropy(x, label, *, soft_label=False, ignore_index=-100):
    """x is a probability distribution (post-softmax), fluid semantics
    (reference: cross_entropy_op.cc)."""
    eps = 1e-8
    if soft_label:
        return -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    lab = label.squeeze(-1) if label.ndim == x.ndim else label
    picked = jnp.take_along_axis(x, lab[..., None].astype(jnp.int32),
                                 axis=-1)
    loss = -jnp.log(picked + eps)
    if ignore_index >= 0:
        loss = jnp.where((lab == ignore_index)[..., None], 0.0, loss)
    return loss


@functools.lru_cache(maxsize=None)
def _lean_softmax_xent(ignore_index):
    """Hand-written backward for the hard-label softmax+xent chain
    (the same bandwidth discipline as fused_ops._lean_xent): autodiff
    of the softmax+log_softmax composite saves BOTH [N, V] float32
    outputs as residuals and rebuilds dlogits from a scatter; here the
    residuals are (logits, lse) — logits is usually live anyway — and
    the backward is ONE fusion: ``dlogits = sm*(g_sm - <g_sm, sm>) +
    (sm - onehot)*g_loss`` with the one-hot as an iota compare. The
    label rides as float32 through the custom_vjp boundary (the float0
    dance — see ops/pallas/attention.py seed_f)."""

    from jax.custom_derivatives import SymbolicZero

    def _core(logits, lab_f):
        x = logits.astype(jnp.float32)
        m = jnp.max(x, axis=-1, keepdims=True)
        e = jnp.exp(x - m)
        s = jnp.sum(e, axis=-1, keepdims=True)
        lse = m + jnp.log(s)
        sm = e / s
        lab = lab_f.astype(jnp.int32)
        picked = jnp.take_along_axis(x, lab, axis=-1)
        loss = lse - picked
        if ignore_index >= 0:
            loss = jnp.where(lab == ignore_index, 0.0, loss)
        return (sm.astype(logits.dtype), loss), (logits, lse, lab_f)

    @jax.custom_vjp
    def f(logits, lab_f):
        return _core(logits, lab_f)[0]

    def fwd(logits_p, lab_p):
        # symbolic_zeros=True wraps primals in CustomVJPPrimal
        return _core(logits_p.value, lab_p.value)

    def _bwd(res, gs):
        logits, lse, lab_f = res
        g_sm, g_loss = gs
        lab = lab_f.astype(jnp.int32)
        sm = jnp.exp(logits.astype(jnp.float32) - lse)
        d = None
        # symbolic-zero cotangents (the common loss-only training
        # case leaves g_sm a SymbolicZero) skip their whole [N, V]
        # term — XLA does not fold float multiplies by zero
        if not isinstance(g_loss, SymbolicZero):
            gl = g_loss.astype(jnp.float32)
            if ignore_index >= 0:
                gl = jnp.where(lab == ignore_index, 0.0, gl)
            # one-hot via iota compare — variable-index scatters
            # serialize on TPU (see fused_ops._lean_xent)
            hot = (lax.broadcasted_iota(jnp.int32, logits.shape,
                                        logits.ndim - 1) == lab)
            d = (sm - hot.astype(jnp.float32)) * gl
        if not isinstance(g_sm, SymbolicZero):
            gsm = g_sm.astype(jnp.float32)
            t = sm * (gsm - jnp.sum(gsm * sm, axis=-1,
                                    keepdims=True))
            d = t if d is None else d + t
        if d is None:
            return (jnp.zeros_like(logits),
                    jnp.zeros_like(lab_f))
        return d.astype(logits.dtype), jnp.zeros_like(lab_f)

    f.defvjp(fwd, _bwd, symbolic_zeros=True)
    return f


@register("softmax_with_cross_entropy", ["Logits", "Label"],
          ["Softmax", "Loss"], nondiff=("Label",))
def softmax_with_cross_entropy(logits, label, *, soft_label=False,
                               ignore_index=-100, axis=-1,
                               return_softmax=True,
                               numeric_stable_mode=True):
    from ..core.flags import FLAGS
    # Internals run in float32 regardless of input dtype (loss stays
    # f32; the softmax output follows the input dtype) — that is what
    # makes the op AMP-gray-safe: bf16 activations enter directly,
    # like layer_norm (fp16_lists.py).
    if soft_label:
        x32 = logits.astype(jnp.float32)
        sm = jax.nn.softmax(x32, axis=axis)
        logp = jax.nn.log_softmax(x32, axis=axis)
        loss = -jnp.sum(label.astype(jnp.float32) * logp, axis=axis,
                        keepdims=True)
        return sm.astype(logits.dtype), loss
    if FLAGS.lean_xent_grad and axis in (-1, logits.ndim - 1):
        lab = label.squeeze(axis) if label.ndim == logits.ndim \
            else label
        return _lean_softmax_xent(int(ignore_index))(
            logits, lab[..., None].astype(jnp.float32))
    x32 = logits.astype(jnp.float32)
    sm = jax.nn.softmax(x32, axis=axis)
    logp = jax.nn.log_softmax(x32, axis=axis)
    lab = label.squeeze(axis) if label.ndim == logits.ndim else label
    picked = jnp.take_along_axis(logp, lab[..., None].astype(jnp.int32),
                                 axis=axis)
    loss = -picked
    if ignore_index >= 0:
        loss = jnp.where((lab == ignore_index)[..., None], 0.0, loss)
    return sm.astype(logits.dtype), loss


@register("sigmoid_cross_entropy_with_logits", ["X", "Label"], ["Out"],
          nondiff=("Label",))
def sigmoid_cross_entropy_with_logits(x, label, *, ignore_index=-100,
                                      normalize=False):
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    if ignore_index >= 0:
        mask = (label != ignore_index).astype(x.dtype)
        loss = loss * mask
        if normalize:
            loss = loss / jnp.maximum(jnp.sum(mask), 1.0)
    return loss


@register("square_error_cost", ["X", "Y"], ["Out"])
def square_error_cost(x, y):
    return jnp.square(x - y)


@register("smooth_l1_loss", ["X", "Y"], ["Out"])
def smooth_l1(x, y, *, sigma=1.0):
    s2 = sigma * sigma
    d = x - y
    ad = jnp.abs(d)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * d * d, ad - 0.5 / s2)
    return jnp.sum(loss, axis=-1, keepdims=True)


@register("huber_loss", ["X", "Y"], ["Out"])
def huber_loss(x, y, *, delta=1.0):
    d = y - x
    ad = jnp.abs(d)
    return jnp.where(ad <= delta, 0.5 * d * d,
                     delta * (ad - 0.5 * delta))


@register("kldiv_loss", ["X", "Target"], ["Loss"], nondiff=("Target",))
def kldiv_loss(x, target, *, reduction="mean"):
    loss = target * (jnp.log(jnp.maximum(target, 1e-10)) - x)
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    if reduction == "batchmean":
        return jnp.sum(loss) / x.shape[0]
    return loss


@register("log_loss", ["Predicted", "Labels"], ["Loss"],
          nondiff=("Labels",))
def log_loss(pred, label, *, epsilon=1e-4):
    return (-label * jnp.log(pred + epsilon)
            - (1.0 - label) * jnp.log(1.0 - pred + epsilon))


@register("margin_rank_loss", ["X1", "X2", "Label"], ["Out"],
          nondiff=("Label",))
def margin_rank_loss(x1, x2, label, *, margin=0.0):
    return jnp.maximum(0.0, -label * (x1 - x2) + margin)


@register("hinge_loss", ["Logits", "Labels"], ["Loss"], nondiff=("Labels",))
def hinge_loss(logits, labels):
    return jnp.maximum(0.0, 1.0 - (2.0 * labels - 1.0) * logits)


@register("mse_loss", ["X", "Y"], ["Out"])
def mse_loss(x, y):
    return jnp.mean(jnp.square(x - y))


# -- conv / pool ------------------------------------------------------------

def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,) * n


@register("conv2d", ["Input", "Filter"], ["Output"])
def conv2d(x, w, *, strides=(1, 1), paddings=(0, 0), dilations=(1, 1),
           groups=1, data_format="NCHW"):
    """Reference: conv_op.cc / conv_cudnn_op.cu.cc:68. Lowered to one
    lax.conv_general_dilated — XLA picks the MXU tiling (the analog of
    cuDNN algo search at :139-151 is done by the compiler)."""
    strides, dilations = _pair(strides), _pair(dilations)
    p = _pair(paddings)
    if len(p) == 2:
        pad = [(p[0], p[0]), (p[1], p[1])]
    else:
        pad = [(p[0], p[1]), (p[2], p[3])]
    dn = lax.conv_dimension_numbers(
        x.shape, w.shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW"
        else ("NHWC", "HWIO", "NHWC"))
    # NOTE: no preferred_element_type here — requesting an f32 output
    # from a bf16 conv breaks JAX's transpose rule under AMP (the
    # backward conv then mixes bf16/f32 operands). The MXU accumulates
    # in f32 internally either way; the output rounds to the input
    # dtype like every other white-list matmul op.
    return lax.conv_general_dilated(
        x, w, window_strides=strides, padding=pad,
        rhs_dilation=dilations, dimension_numbers=dn,
        feature_group_count=groups)


@register("depthwise_conv2d", ["Input", "Filter"], ["Output"])
def depthwise_conv2d(x, w, *, strides=(1, 1), paddings=(0, 0),
                     dilations=(1, 1), groups=None, data_format="NCHW"):
    g = groups or x.shape[1]
    return conv2d(x, w, strides=strides, paddings=paddings,
                  dilations=dilations, groups=g, data_format=data_format)


@register("conv3d", ["Input", "Filter"], ["Output"])
def conv3d(x, w, *, strides=(1, 1, 1), paddings=(0, 0, 0),
           dilations=(1, 1, 1), groups=1):
    strides = _pair(strides, 3)
    dilations = _pair(dilations, 3)
    p = _pair(paddings, 3)
    pad = [(pi, pi) for pi in p]
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NCDHW", "OIDHW", "NCDHW"))
    return lax.conv_general_dilated(x, w, window_strides=strides,
                                    padding=pad, rhs_dilation=dilations,
                                    dimension_numbers=dn,
                                    feature_group_count=groups)


@register("conv2d_transpose", ["Input", "Filter"], ["Output"])
def conv2d_transpose(x, w, *, strides=(1, 1), paddings=(0, 0),
                     dilations=(1, 1), groups=1, output_size=None):
    """Gradient-of-conv semantics: out = (H-1)*stride - 2*pad +
    dilation*(k-1) + 1 (reference: conv_transpose_op.cc). Lowered to an
    input-dilated conv with per-side pads of dilation*(k-1) - pad."""
    strides, dilations = _pair(strides), _pair(dilations)
    p = _pair(paddings)
    ks = w.shape[2:]
    pad = [(dilations[i] * (ks[i] - 1) - p[i],) * 2 for i in range(2)]
    # fluid filter layout for transpose: (in, out//groups, kh, kw).
    # Deconv = conv of the input dilated by `strides` with the spatially
    # flipped kernel; the IOHW dimension spec swaps in/out channels.
    w_flip = jnp.flip(w, axis=(2, 3))
    if groups == 1:
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCHW", "IOHW", "NCHW"))
        return lax.conv_general_dilated(
            x, w_flip, window_strides=(1, 1), padding=pad,
            lhs_dilation=strides, rhs_dilation=dilations,
            dimension_numbers=dn)
    # Grouped deconv: (g*in_g, out_g, kh, kw) -> (g*out_g, in_g, kh,
    # kw) OIHW so lax's consecutive-block group semantics line up with
    # fluid's consecutively-grouped output channels.
    cin, out_g, kh, kw = w_flip.shape
    in_g = cin // groups
    w_oihw = (w_flip.reshape(groups, in_g, out_g, kh, kw)
              .transpose(0, 2, 1, 3, 4)
              .reshape(groups * out_g, in_g, kh, kw))
    dn = lax.conv_dimension_numbers(x.shape, w_oihw.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(
        x, w_oihw, window_strides=(1, 1), padding=pad,
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=dn, feature_group_count=groups)


@register("depthwise_conv2d_transpose", ["Input", "Filter"], ["Output"])
def depthwise_conv2d_transpose(x, w, *, strides=(1, 1), paddings=(0, 0),
                               dilations=(1, 1), groups=None,
                               output_size=None):
    """Reference: conv_transpose_op.cc (depthwise variant). Per-channel
    transposed conv: groups defaults to the input channel count."""
    g = groups or x.shape[1]
    return conv2d_transpose(x, w, strides=strides, paddings=paddings,
                            dilations=dilations, groups=g,
                            output_size=output_size)


@register("pool2d", ["X"], ["Out"])
def pool2d(x, *, ksize, pooling_type="max", strides=(1, 1),
           paddings=(0, 0), global_pooling=False, ceil_mode=False,
           exclusive=True, adaptive=False, data_format="NCHW"):
    """Reference: pool_op.cc. Lowered to lax.reduce_window; NHWC runs
    through a transpose pair XLA folds into the window layout."""
    if data_format == "NHWC":
        out = pool2d(x.transpose(0, 3, 1, 2), ksize=ksize,
                     pooling_type=pooling_type, strides=strides,
                     paddings=paddings, global_pooling=global_pooling,
                     ceil_mode=ceil_mode, exclusive=exclusive,
                     adaptive=adaptive, data_format="NCHW")
        return out.transpose(0, 2, 3, 1)
    if data_format != "NCHW":
        raise InvalidArgumentError(
            "pool2d data_format must be NCHW or NHWC, got %r"
            % (data_format,))
    if global_pooling or adaptive and tuple(_pair(ksize)) == (1, 1):
        axis = (2, 3)
        if pooling_type == "max":
            return jnp.max(x, axis=axis, keepdims=True)
        return jnp.mean(x, axis=axis, keepdims=True)
    k = _pair(ksize)
    s = _pair(strides)
    p = _pair(paddings)
    window = (1, 1) + k
    stride = (1, 1) + s
    hi = [p[0], p[1]]
    if ceil_mode:
        # reference pool_op.cc ceil formula: output covers the input
        # tail by padding the high side up to a full extra stride
        for i, (L, kk, ss, pp) in enumerate(
                zip(x.shape[2:], k, s, p)):
            out_ceil = -(-(L + 2 * pp - kk) // ss) + 1
            hi[i] = (out_ceil - 1) * ss + kk - (L + pp)
    pads = [(0, 0), (0, 0), (p[0], hi[0]), (p[1], hi[1])]
    if pooling_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, stride, pads)
    # avg pool
    ones = jnp.ones_like(x)
    summed = lax.reduce_window(x, 0.0, lax.add, window, stride, pads)
    if exclusive:
        # padding contributes 0 to counts, so ceil-mode tail windows
        # divide by their real element count
        counts = lax.reduce_window(ones, 0.0, lax.add, window, stride,
                                   pads)
    else:
        counts = float(k[0] * k[1])
    return summed / counts


def _adaptive_pool(x, out_sizes, axes, pooling_type):
    """General adaptive pooling: output cell i over axis of length L
    covers [floor(i*L/O), ceil((i+1)*L/O)) — the reference's
    AdaptiveStartIndex/AdaptiveEndIndex (pool_op.h:42-52). Bin
    boundaries are static, so uneven sizes lower to a static slice
    per cell (cheap: O cells is small); the even case keeps the fused
    one-reshape reduction."""
    if all(x.shape[ax] % o == 0 for ax, o in zip(axes, out_sizes)):
        shape, red_axes = [], []
        for d in range(x.ndim):
            if d in axes:
                o = out_sizes[axes.index(d)]
                shape += [o, x.shape[d] // o]
                red_axes.append(len(shape) - 1)
            else:
                shape.append(x.shape[d])
        xr = x.reshape(shape)
        reduce = jnp.max if pooling_type == "max" else jnp.mean
        return reduce(xr, axis=tuple(red_axes))

    def pool_axis(arr, ax, o):
        L = arr.shape[ax]
        cells = []
        for i in range(o):
            lo, hi = (i * L) // o, -((-(i + 1) * L) // o)  # ceil
            sl = [slice(None)] * arr.ndim
            sl[ax] = slice(lo, hi)
            reduce = jnp.max if pooling_type == "max" else jnp.mean
            cells.append(reduce(arr[tuple(sl)], axis=ax,
                                keepdims=True))
        return jnp.concatenate(cells, axis=ax)

    for ax, o in zip(axes, out_sizes):
        x = pool_axis(x, ax, o)
    return x


@register("adaptive_pool2d", ["X"], ["Out"])
def adaptive_pool2d(x, *, pool_size, pooling_type="avg"):
    oh, ow = _pair(pool_size)
    return _adaptive_pool(x, (oh, ow), (2, 3), pooling_type)


# -- normalization ----------------------------------------------------------

@register("batch_norm",
          ["X", "Scale", "Bias", "Mean", "Variance"],
          ["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"],
          nondiff=("Mean", "Variance"))
def batch_norm(x, scale, bias, mean, var, *, epsilon=1e-5, momentum=0.9,
               is_test=False, data_layout="NCHW", use_global_stats=False):
    """Reference: batch_norm_op.cc/.cu. Running stats are persistable vars
    updated functionally (MeanOut/VarianceOut alias Mean/Variance in the
    program, as the reference does)."""
    axes = (0, 2, 3) if (x.ndim == 4 and data_layout == "NCHW") else \
        tuple(i for i in range(x.ndim) if i != x.ndim - 1) \
        if data_layout == "NHWC" else (0,)
    if x.ndim == 2:
        axes = (0,)
    bshape = [1] * x.ndim
    caxis = 1 if (data_layout == "NCHW" and x.ndim == 4) else x.ndim - 1
    if x.ndim == 2:
        caxis = 1
    bshape[caxis] = x.shape[caxis]

    def _r(v):
        return v.reshape(bshape)

    if is_test or use_global_stats:
        y = ((x.astype(jnp.float32) - _r(mean)) * _r(scale) *
             lax.rsqrt(_r(var) + epsilon) +
             _r(bias)).astype(x.dtype)
        return y, mean, var, mean, var
    # Statistics ALWAYS in f32 (the reference's fp16 BN keeps float
    # accumulators, batch_norm_op.cu): the one-pass E[x^2]-E[x]^2 form
    # in bf16 cancels catastrophically (negative variance -> rsqrt
    # NaN under AMP). Two-pass + f32 is cheap and stable.
    xf = x.astype(jnp.float32)
    bmean = jnp.mean(xf, axis=axes)
    bvar = jnp.mean(jnp.square(xf - _r(bmean)), axis=axes)
    y = ((xf - _r(bmean)) * _r(scale) *
         lax.rsqrt(_r(bvar) + epsilon) + _r(bias)).astype(x.dtype)
    mean_out = momentum * mean + (1.0 - momentum) * bmean
    var_out = momentum * var + (1.0 - momentum) * bvar
    return y, mean_out, var_out, bmean, bvar


@jax.custom_vjp
def _ln_affine(norm, scale, bias):
    """custom-vjp affine tail of layer_norm (FLAGS.mxu_ln_grad): the
    dScale/dBias column reductions over N rows run as ones@M MXU dots
    with f32 accumulation instead of convert_reduce fusions (their
    share of the step on this installation: not measured). Same
    treatment as ops/math_ops._bias_add_vjp, extended to the scale
    product. dX path (through mean/var) stays autodiff. scale/bias
    arrive already broadcast-shaped ([1, ..., D])."""
    return norm * scale + bias


def _ln_affine_fwd(norm, scale, bias):
    return norm * scale + bias, (norm, scale)


def _ln_affine_bwd(res, g):
    norm, scale = res
    dnorm = g * scale
    d = g.shape[-1]
    g2 = g.reshape(-1, d)
    n2 = norm.reshape(-1, d)
    ones = jnp.ones((g2.shape[0],), g2.dtype)
    dims = (((0,), (0,)), ((), ()))
    dbias = lax.dot_general(ones, g2, dims,
                            preferred_element_type=jnp.float32)
    dscale = lax.dot_general(ones, g2 * n2, dims,
                             preferred_element_type=jnp.float32)
    return (dnorm, dscale.reshape(scale.shape).astype(scale.dtype),
            dbias.reshape(scale.shape).astype(g.dtype))


_ln_affine.defvjp(_ln_affine_fwd, _ln_affine_bwd)


@register("layer_norm", ["X", "Scale", "Bias"], ["Y", "Mean", "Variance"])
def layer_norm(x, scale, bias, *, epsilon=1e-5, begin_norm_axis=1):
    """Reference: layer_norm_op.cc. Normalizes over dims
    [begin_norm_axis:]; pallas variant registered in ops/pallas.

    Statistics in f32 regardless of input dtype (bf16 moment sums lose
    precision), output back in the INPUT dtype — under AMP this keeps
    the bf16 stream flowing instead of shipping f32 activations to the
    next matmul's cast (the same policy as batch_norm)."""
    from ..core.flags import FLAGS
    axes = tuple(range(begin_norm_axis, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    inv = lax.rsqrt(var + epsilon)
    norm = (xf - mean) * inv
    bshape = [1] * begin_norm_axis + list(x.shape[begin_norm_axis:])
    if (FLAGS.mxu_ln_grad and scale is not None and bias is not None
            and len(axes) == 1 and x.shape[-1] == scale.shape[-1]):
        norm = _ln_affine(norm,
                          scale.reshape(bshape).astype(norm.dtype),
                          bias.reshape(bshape).astype(norm.dtype))
        return norm.astype(x.dtype), jnp.squeeze(mean), jnp.squeeze(var)
    if scale is not None:
        norm = norm * scale.reshape(bshape)
    if bias is not None:
        norm = norm + bias.reshape(bshape)
    return norm.astype(x.dtype), jnp.squeeze(mean), jnp.squeeze(var)


@register("rms_norm", ["X", "Scale"], ["Y"])
def rms_norm(x, scale, *, epsilon=1e-5):
    """``x / sqrt(mean(x^2) + epsilon) * scale`` over the last axis (no
    mean subtracted, no bias). The mean square in float32 whatever the
    input's type, the output back in the INPUT's type: layer_norm's
    policy, so under AMP the bf16 stream goes on."""
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                    + epsilon)
    return (xf * inv * scale.astype(jnp.float32)).astype(x.dtype)


_ROTARY_PATHS = ("whole_half", "whole_interleaved", "partial_half",
                 "partial_interleaved")


@register("rotary_embedding", ["X"], ["Out"])
def rotary_embedding(x, *, theta=10000.0, start=0, width=0,
                     interleaved=False):
    """Rotary position embedding of lanes ``[start, start + width)`` of
    the last axis (``width`` 0: all that follow ``start``), the other
    lanes passed through: x [B, H, S, Dh], position s of row s. Pair i
    of the ``width // 2`` turns by ``s * theta^(-2i/width)``; it is
    the lanes (i, i + width/2) of the part in the rotate-half layout
    and (2i, 2i + 1) with ``interleaved``, each turned in place. The
    angles are float32 constants of the trace; the output keeps x's
    type. Each lowering bumps ``rotary_lowering.<whole|partial>_
    <half|interleaved>`` (the paths not taken listed with 0)."""
    # the kernel package registers variants of this module's ops
    from .pallas.common import count_lowering
    s, dh = x.shape[-2], x.shape[-1]
    width = width or dh - start
    if start < 0 or width % 2 or start + width > dh:
        raise ValueError("rotary_embedding: lanes [%d, %d) of %d in "
                         "pairs" % (start, start + width, dh))
    took = "%s_%s" % ("whole" if width == dh else "partial",
                      "interleaved" if interleaved else "half")
    for path in _ROTARY_PATHS:
        count_lowering("rotary_lowering." + path, float(path == took))
    half = width // 2
    if took == "whole_half":
        # the form ``models/afmoe.py``'s sliding layers have lowered to
        # since PR 28, kept word for word: their executables' keys hold
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32)
                             * (2.0 / width))
        ang = jnp.arange(s, dtype=jnp.float32)[:, None] \
            * inv_freq[None, :]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)
    # any other part or layout: each lane of the part times its pair's
    # cosine plus its partner times the sine. The partners come from a
    # product with a constant signed permutation (one +-1 a column, so
    # exact in x's own type): the lanes are never reshaped into pairs,
    # which would pad every pair to a tile on the TPU
    lane = np.arange(width)
    pair, first, mate = (lane // 2, lane % 2 == 0, lane ^ 1) \
        if interleaved else (lane % half, lane < half,
                             (lane + half) % width)
    swap = np.zeros((width, width), np.float32)
    swap[mate, lane] = np.where(first, -1.0, 1.0)
    part = x[..., start:start + width]
    partner = jnp.matmul(
        part, jnp.asarray(swap, x.dtype), preferred_element_type=x.dtype,
        precision=lax.Precision.HIGHEST if x.dtype == jnp.float32
        else None)
    inv_freq = theta ** (-jnp.asarray(pair, jnp.float32) * (2.0 / width))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    turned = part.astype(jnp.float32) * jnp.cos(ang) \
        + partner.astype(jnp.float32) * jnp.sin(ang)
    pieces = [x[..., :start], turned.astype(x.dtype),
              x[..., start + width:]]
    return jnp.concatenate([p for p in pieces if p.shape[-1]], axis=-1)


@register("group_norm", ["X", "Scale", "Bias"], ["Y", "Mean", "Variance"])
def group_norm(x, scale, bias, *, groups, epsilon=1e-5):
    n, c, h, w = x.shape
    g = groups
    xg = x.reshape(n, g, c // g, h, w)
    mean = jnp.mean(xg, axis=(2, 3, 4), keepdims=True)
    var = jnp.var(xg, axis=(2, 3, 4), keepdims=True)
    xn = ((xg - mean) * lax.rsqrt(var + epsilon)).reshape(n, c, h, w)
    if scale is not None:
        xn = xn * scale.reshape(1, c, 1, 1)
    if bias is not None:
        xn = xn + bias.reshape(1, c, 1, 1)
    return xn, jnp.squeeze(mean), jnp.squeeze(var)


@register("instance_norm", ["X", "Scale", "Bias"], ["Y"])
def instance_norm(x, scale, bias, *, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + epsilon)
    c = x.shape[1]
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return y


@register("l2_normalize", ["X"], ["Out"])
def l2_normalize(x, *, axis=-1, epsilon=1e-12):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True)
                         + epsilon)


# -- dropout / embedding ----------------------------------------------------

@register("dropout", ["X"], ["Out", "Mask"], needs_rng=True)
def dropout(x, *, dropout_prob=0.5, is_test=False,
            dropout_implementation="downgrade_in_infer", seed=0, rng=None):
    """Reference: dropout_op.cc. Counter-based RNG replaces curand.

    The backward RECOMPUTES the keep mask from the saved key instead of
    keeping the full-tensor mask live from forward to backward — with
    the counter-based generator the bits cost a few fused vector ops,
    while a saved mask costs a full HBM round-trip per dropout site
    (~30 sites x [16k, 512]+ on transformer-base). The Mask output is
    still emitted for API parity; XLA CSEs it against the forward's
    in-register mask and dead-codes it when nothing consumes it."""
    if is_test:
        if dropout_implementation == "upscale_in_train":
            return x, jnp.ones_like(x)
        return x * (1.0 - dropout_prob), jnp.ones_like(x)
    key = jax.random.key(seed) if seed else rng
    upscale = dropout_implementation == "upscale_in_train"
    out = _dropout_train(float(dropout_prob), upscale)(x, key)
    mask = _keep_mask(key, dropout_prob, x.shape).astype(x.dtype)
    return out, mask


@functools.lru_cache(maxsize=None)
def _dropout_train(rate, upscale):
    @jax.custom_vjp
    def f(x, key):
        mask = _keep_mask(key, rate, x.shape).astype(x.dtype)
        return x * mask / (1.0 - rate) if upscale else x * mask

    def fwd(x, key):
        return f(x, key), (key,)

    def bwd(res, g):
        (key,) = res
        mask = _keep_mask(key, rate, g.shape).astype(g.dtype)
        dx = g * mask / (1.0 - rate) if upscale else g * mask
        return dx, None

    f.defvjp(fwd, bwd)
    return f


def _keep_mask(key, rate, shape):
    """Bernoulli(1-rate) keep mask by raw-bit threshold compare.

    Equivalent to jax.random.bernoulli (bits uniform, so
    P[bits >= rate*2^B] = 1-rate to within 2^-B) but skips the
    bits->float-uniform conversion — on the bench transformer the mask
    generation over the [B,H,S,S] attention weights and FFN
    activations is ~1/5 of step time, so the elementwise work here is
    a measured win. (A u16-halves variant — one generated u32 serving
    two elements — was chip-measured in round 4 and did NOT win: the
    bitcast+reshape breaks the generator's fusion with the consumer,
    and the rbg generator is not bit-count-bound.) RNG impl is
    whatever jax.random.bits uses (rbg on TPU via bench.py)."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    thresh = min(int(rate * (1 << 32)), (1 << 32) - 1)
    return bits >= jnp.uint32(thresh)


@register("lookup_table", ["W", "Ids"], ["Out"], nondiff=("Ids",))
def lookup_table(w, ids, *, padding_idx=-1, is_sparse=False,
                 is_distributed=False):
    """Embedding lookup (reference: lookup_table_op.cc). On TPU this is a
    dense HBM gather; XLA emits an efficient dynamic-gather. Sparse-grad
    handling (SelectedRows) is subsumed by XLA scatter-add in the VJP."""
    ids2 = ids.squeeze(-1) if ids.ndim > 1 and ids.shape[-1] == 1 else ids
    out = jnp.take(w, ids2, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((ids2 == padding_idx)[..., None], 0.0, out)
    return out


@register("lookup_table_grad", ["Ids", "OutGrad"], ["WGrad"],
          differentiable=False, accumulate_outputs=True)
def lookup_table_grad(ids, out_grad, *, height, padding_idx=-1):
    """Sparse gradient of lookup_table (reference: lookup_table_op.cc
    ``is_sparse`` grad path emitting SelectedRows). Appended by
    backward.append_backward instead of a generic vjp op when the
    forward lookup has is_sparse=True: the table gradient is the
    incoming cotangent re-labelled with its row ids — O(batch), no
    scatter, and the [height, dim] table is never densified."""
    from ..core.selected_rows import SparseRows

    ids2 = ids.squeeze(-1) if ids.ndim > 1 and ids.shape[-1] == 1 \
        else ids
    rows = ids2.reshape(-1).astype(jnp.int32)
    dim = out_grad.shape[-1]
    values = out_grad.reshape(-1, dim)
    if padding_idx is not None and padding_idx >= 0:
        # forward zeroed padding rows; their cotangent must not flow
        values = jnp.where((rows == padding_idx)[:, None], 0.0, values)
    return SparseRows(rows, values, height)


@register("embedding_bag", ["W", "Ids"], ["Out"], nondiff=("Ids",))
def embedding_bag(w, ids, *, mode="sum", padding_idx=-1):
    """Fused embedding + sequence-pool (reference:
    fused_embedding_seq_pool_op.cc). ids: [batch, bag]; padding_idx rows
    contribute zero."""
    emb = jnp.take(w, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx).astype(w.dtype)[..., None]
        emb = emb * mask
        denom = jnp.maximum(jnp.sum(mask, axis=1), 1.0)
    else:
        denom = float(ids.shape[1])
    if mode == "sum":
        return jnp.sum(emb, axis=1)
    if mode == "mean":
        return jnp.sum(emb, axis=1) / denom
    return jnp.max(emb, axis=1)


# -- misc -------------------------------------------------------------------

@register("interpolate", ["X"], ["Out"])
def interpolate(x, *, out_shape, method="nearest", align_corners=False,
                data_format="NCHW"):
    n, c, h, w = x.shape
    oh, ow = out_shape
    return jax.image.resize(x, (n, c, oh, ow),
                            method="nearest" if method == "nearest"
                            else "bilinear")


@register("pixel_shuffle", ["X"], ["Out"])
def pixel_shuffle(x, *, upscale_factor):
    n, c, h, w = x.shape
    r = upscale_factor
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return x.reshape(n, c // (r * r), h * r, w * r)


@register("grid_sampler", ["X", "Grid"], ["Output"])
def grid_sampler(x, grid):
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[..., 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx, wy = gx - x0, gy - y0

    def _sample(xi, yi):
        xi = jnp.clip(xi, 0, w - 1)
        yi = jnp.clip(yi, 0, h - 1)
        batch_idx = jnp.arange(n)[:, None, None]
        return x[batch_idx, :, yi, xi]  # [n, oh, ow, c]

    v00 = _sample(x0, y0)
    v01 = _sample(x1, y0)
    v10 = _sample(x0, y1)
    v11 = _sample(x1, y1)
    wx_, wy_ = wx[..., None], wy[..., None]
    out = (v00 * (1 - wx_) * (1 - wy_) + v01 * wx_ * (1 - wy_)
           + v10 * (1 - wx_) * wy_ + v11 * wx_ * wy_)
    return jnp.transpose(out, (0, 3, 1, 2))


@register("fsp_matrix", ["X", "Y"], ["Out"])
def fsp_matrix(x, y):
    """Reference: operators/fsp_op.cc — flow-of-solution-procedure
    matrix between two [b, c1, h, w] / [b, c2, h, w] feature maps:
    Out[b, i, j] = sum_hw X[b,i,h,w] * Y[b,j,h,w] / (h*w). One MXU
    einsum on TPU."""
    h, w = x.shape[2], x.shape[3]
    return jnp.einsum("bihw,bjhw->bij", x, y) / float(h * w)


@register("label_smooth", ["X", "PriorDist"], ["Out"])
def label_smooth(x, prior_dist=None, *, epsilon=0.1):
    """Reference: operators/label_smooth_op.cc — uniform (or prior)
    smoothing of one-hot targets."""
    k = x.shape[-1]
    if prior_dist is not None:
        return (1.0 - epsilon) * x + epsilon * prior_dist
    return (1.0 - epsilon) * x + epsilon / k


@register("brelu", ["X"], ["Out"])
def brelu(x, *, t_min=0.0, t_max=24.0):
    """Reference: operators/activation_op.cc BRelu."""
    return jnp.clip(x, t_min, t_max)


@register("soft_relu", ["X"], ["Out"])
def soft_relu(x, *, threshold=40.0):
    """Reference: activation_op.cc SoftRelu: log(1 + exp(clip(x)))."""
    return jnp.log1p(jnp.exp(jnp.clip(x, -threshold, threshold)))


@register("stanh", ["X"], ["Out"])
def stanh(x, *, scale_a=0.67, scale_b=1.7159):
    """Reference: activation_op.cc STanh."""
    return scale_b * jnp.tanh(scale_a * x)


@register("adaptive_pool3d", ["X"], ["Out"])
def adaptive_pool3d(x, *, pool_size, pooling_type="avg"):
    """Reference: pool_op.cc adaptive 3-D (NCDHW); uneven splits use
    the reference's floor/ceil bin boundaries (pool_op.h:42-52)."""
    od, oh, ow = (pool_size if isinstance(pool_size, (list, tuple))
                  else (pool_size,) * 3)
    return _adaptive_pool(x, (od, oh, ow), (2, 3, 4), pooling_type)


@register("dice_loss", ["X", "Label"], ["Out"], nondiff=("Label",))
def dice_loss(x, label, *, epsilon=1e-5):
    """Reference: layers/nn.py dice_loss (composite in the reference
    python layer): 1 - 2*|X∩L| / (|X|+|L|), reduced over all but the
    batch dim."""
    label = label.astype(x.dtype)
    reduce_dims = tuple(range(1, x.ndim))
    inter = jnp.sum(x * label, axis=reduce_dims)
    union = jnp.sum(x, axis=reduce_dims) + jnp.sum(label,
                                                   axis=reduce_dims)
    return jnp.mean(1.0 - (2.0 * inter + epsilon) / (union + epsilon))


@register("npair_loss", ["Anchor", "Positive", "Labels"], ["Out"],
          nondiff=("Labels",))
def npair_loss(anchor, positive, labels, *, l2_reg=0.002):
    """Reference: layers/loss.py npair_loss composite — softmax
    cross-entropy over anchor·positiveᵀ similarities with same-label
    targets, plus l2 regularization on the embeddings."""
    sim = jnp.dot(anchor, positive.T)                   # [B, B]
    lab = labels.reshape(-1)
    same = (lab[:, None] == lab[None, :]).astype(sim.dtype)
    tgt = same / jnp.maximum(jnp.sum(same, axis=1, keepdims=True),
                             1.0)
    logp = jax.nn.log_softmax(sim, axis=1)
    ce = -jnp.mean(jnp.sum(tgt * logp, axis=1))
    reg = l2_reg * (jnp.mean(jnp.sum(jnp.square(anchor), axis=1))
                    + jnp.mean(jnp.sum(jnp.square(positive),
                                       axis=1))) / 2.0
    return ce + reg


@register("similarity_focus", ["X"], ["Out"], differentiable=False)
def similarity_focus(x, *, axis, indexes):
    """Reference: operators/similarity_focus_op.cc — build a 0/1
    focus mask: for each selected channel index along ``axis``, mark
    the argmax positions per remaining row/col (NCHW only, axis=1 as
    the reference supports)."""
    n, c, h, w = x.shape
    out = jnp.zeros_like(x)
    for idx in indexes:
        sl = x[:, idx]                                  # [N, H, W]
        row_best = jnp.argmax(sl, axis=2)               # [N, H]
        col_best = jnp.argmax(sl, axis=1)               # [N, W]
        mask = jnp.zeros((n, h, w), x.dtype)
        mask = mask.at[jnp.arange(n)[:, None],
                       jnp.arange(h)[None, :], row_best].set(1.0)
        mask = mask.at[jnp.arange(n)[:, None], col_best,
                       jnp.arange(w)[None, :]].set(1.0)
        out = out + mask[:, None, :, :] * jnp.ones((1, c, 1, 1),
                                                   x.dtype)
    return jnp.minimum(out, 1.0)
