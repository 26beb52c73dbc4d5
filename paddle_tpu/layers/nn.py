"""User-facing layers API — parametric layers and NN ops.

Reference: python/paddle/fluid/layers/nn.py (12k LoC, 171 defs: fc:211,
embedding, conv2d, pool2d, batch_norm, layer_norm, dropout, ...). Same
names and signatures (modulo LoD-specific args); each call appends ops to
the default main program via LayerHelper.
"""

from __future__ import annotations

from .. import framework
from ..core.enforce import enforce
from ..framework import Variable
from ..layer_helper import LayerHelper
from ..initializer import Constant


def _simple(op_type, x, attrs=None, name=None, extra_inputs=None,
            out_dtype=None, stop_gradient=False):
    helper = LayerHelper(op_type, name=name)
    inputs = {"X": [x]}
    if extra_inputs:
        inputs.update(extra_inputs)
    out = helper.create_variable_for_type_inference(
        out_dtype or x.dtype, stop_gradient=stop_gradient)
    helper.append_op(type=op_type, inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs or {})
    return out


# ---------------------------------------------------------------------------
# fc / embedding
# ---------------------------------------------------------------------------

def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """Fully-connected layer (reference: layers/nn.py:211). Multiple
    inputs are each projected then summed, as in fluid."""
    helper = LayerHelper("fc", name=name, act=act)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    param_attrs = param_attr if isinstance(param_attr, (list, tuple)) \
        else [param_attr] * len(inputs)
    mul_results = []
    for inp, pattr in zip(inputs, param_attrs):
        enforce(
            inp.shape is not None
            and len(inp.shape) > num_flatten_dims,
            "fc input %r needs a known rank > num_flatten_dims=%d to "
            "size its weight (got shape %r — if this is an op whose "
            "shape inference failed, set FLAGS_infer_shape_debug=1 to "
            "see why)" % (inp.name, num_flatten_dims, inp.shape))
        in_features = 1
        for d in inp.shape[num_flatten_dims:]:
            in_features *= d
        w = helper.create_parameter(attr=pattr,
                                    shape=(in_features, size),
                                    dtype=inp.dtype)
        out = helper.create_variable_for_type_inference(inp.dtype)
        helper.append_op(type="mul", inputs={"X": [inp], "Y": [w]},
                         outputs={"Out": [out]},
                         attrs={"x_num_col_dims": num_flatten_dims,
                                "y_num_col_dims": 1})
        mul_results.append(out)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(
            inputs[0].dtype)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr, shape=(size,),
                                    dtype=pre_bias.dtype, is_bias=True)
        pre_act = helper.append_bias_op(pre_bias, b,
                                        axis=num_flatten_dims)
    else:
        pre_act = pre_bias
    return helper.append_activation(pre_act)


def fused_linear_cross_entropy(input, label, size, epsilon=0.0,
                               param_attr=None, name=None,
                               return_logits=False):
    """Fused vocabulary projection + label-smoothed softmax
    cross-entropy over the last axis of ``input``:
    ``loss = softmax_xent(input @ W, smooth(onehot(label), epsilon))``.

    The TPU replacement for the ``fc + label_smooth +
    softmax_with_cross_entropy`` chain every NMT/LM model ends with
    (reference: operators/fused/ fusion pattern + math/cross_entropy.cu)
    — the [N, vocab] logits are the model's largest activation, and the
    fused op (pallas variant: ops/pallas/fused_xent.py) streams them
    through VMEM instead of materializing them in HBM.

    ``return_logits=True`` additionally emits the plain logits through
    a separate mul on the same weight — for inference graphs; when the
    logits go unfetched at train time XLA dead-code-eliminates the
    extra matmul, so emitting both costs nothing.

    Returns ``loss`` ([..., 1] float32), or ``(loss, logits)``.
    """
    helper = LayerHelper("fused_linear_xent", name=name)
    in_features = input.shape[-1]
    w = helper.create_parameter(attr=param_attr,
                                shape=(in_features, size),
                                dtype=input.dtype)
    loss = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="fused_linear_xent",
                     inputs={"X": [input], "W": [w], "Label": [label]},
                     outputs={"Loss": [loss]},
                     attrs={"epsilon": epsilon})
    if not return_logits:
        return loss
    logits = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="mul", inputs={"X": [input], "Y": [w]},
                     outputs={"Out": [logits]},
                     attrs={"x_num_col_dims": len(input.shape) - 1,
                            "y_num_col_dims": 1})
    return loss, logits


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32",
              name=None):
    """Reference: layers/nn.py embedding -> lookup_table_op.cc. On TPU
    the table is a dense HBM array; ``is_sparse`` is accepted for parity
    (XLA's gather/scatter-add covers the SelectedRows path).

    ``is_distributed=True`` requests a table too large for device HBM:
    no parameter is created — the rows live host-side across pserver
    processes (distributed.LargeScaleKV) and the lookup result enters
    the program as a feed-like data var. The runtime
    (distributed.SparseEmbeddingRuntime) prefetches the batch's rows
    before each step and pushes the sparse grads after — the analog of
    _replace_lookup_table_op_with_prefetch
    (distribute_transpiler.py:1372) + parameter_prefetch.cc."""
    helper = LayerHelper("embedding", name=name)
    if is_distributed:
        from .. import unique_name
        # a ParamAttr name pins the table id across processes (server
        # and trainer must agree on it — same contract as dense param
        # names under unique_name.guard); _to_attr so the plain-str
        # spelling every other layer accepts works here too
        from ..param_attr import ParamAttr
        attr = ParamAttr._to_attr(param_attr) \
            if param_attr is not None else None
        attr_name = attr.name if isinstance(attr, ParamAttr) else None
        table = attr_name or name or unique_name.generate("dist_table")
        out_shape = tuple(input.shape) + (size[1],)
        out = helper.main_program.global_block().create_var(
            name=unique_name.generate(table + "_prefetch"),
            shape=out_shape, dtype=dtype, is_data=True)
        meta = getattr(helper.main_program, "_distributed_lookups", None)
        if meta is None:
            meta = helper.main_program._distributed_lookups = []
        pad = None if padding_idx is None else \
            (padding_idx if padding_idx >= 0 else size[0] + padding_idx)
        meta.append({"table": table, "ids": input.name,
                     "out": out.name, "rows": size[0],
                     "dim": size[1], "padding_idx": pad})
        return out
    w = helper.create_parameter(attr=param_attr, shape=tuple(size),
                                dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    pad = -1 if padding_idx is None else \
        (padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    helper.append_op(type="lookup_table",
                     inputs={"W": [w], "Ids": [input]},
                     outputs={"Out": [out]},
                     attrs={"padding_idx": pad, "is_sparse": is_sparse,
                            "is_distributed": is_distributed})
    return out


# ---------------------------------------------------------------------------
# conv / pool / norm
# ---------------------------------------------------------------------------

def conv2d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           use_cudnn=True, act=None, name=None, data_format="NCHW"):
    """Reference: layers/nn.py conv2d. use_cudnn accepted for parity and
    ignored — XLA owns algorithm choice on TPU."""
    helper = LayerHelper("conv2d", name=name, act=act)

    def _pair(v):
        return tuple(v) if isinstance(v, (list, tuple)) else (v, v)

    fsize = _pair(filter_size)
    channels = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    enforce(channels % groups == 0, "channels %% groups != 0")
    w_shape = (num_filters, channels // groups) + fsize
    from ..initializer import MSRAInitializer
    w = helper.create_parameter(
        attr=param_attr, shape=w_shape, dtype=input.dtype,
        default_initializer=MSRAInitializer(uniform=False))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="conv2d",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": _pair(stride),
                            "paddings": _pair(padding),
                            "dilations": _pair(dilation),
                            "groups": groups,
                            "data_format": data_format})
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr, shape=(num_filters,),
                                    dtype=input.dtype, is_bias=True)
        out = helper.append_bias_op(out, b, axis=1)
    return helper.append_activation(out)


def conv2d_transpose(input, num_filters, filter_size, stride=1, padding=0,
                     dilation=1, groups=1, param_attr=None,
                     bias_attr=None, act=None, name=None,
                     output_size=None):
    helper = LayerHelper("conv2d_transpose", name=name, act=act)

    def _pair(v):
        return tuple(v) if isinstance(v, (list, tuple)) else (v, v)

    fsize = _pair(filter_size)
    channels = input.shape[1]
    w = helper.create_parameter(
        attr=param_attr, shape=(channels, num_filters // groups) + fsize,
        dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="conv2d_transpose",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": _pair(stride),
                            "paddings": _pair(padding),
                            "dilations": _pair(dilation),
                            "groups": groups,
                            "output_size": output_size})
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr, shape=(num_filters,),
                                    dtype=input.dtype, is_bias=True)
        out = helper.append_bias_op(out, b, axis=1)
    return helper.append_activation(out)


def conv3d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           act=None, name=None):
    helper = LayerHelper("conv3d", name=name, act=act)

    def _trip(v):
        return tuple(v) if isinstance(v, (list, tuple)) else (v, v, v)

    fsize = _trip(filter_size)
    channels = input.shape[1]
    w = helper.create_parameter(
        attr=param_attr, shape=(num_filters, channels // groups) + fsize,
        dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="conv3d",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": _trip(stride),
                            "paddings": _trip(padding),
                            "dilations": _trip(dilation),
                            "groups": groups})
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr, shape=(num_filters,),
                                    dtype=input.dtype, is_bias=True)
        out = helper.append_bias_op(out, b, axis=1)
    return helper.append_activation(out)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, exclusive=True, name=None,
           data_format="NCHW"):
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="pool2d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"ksize": pool_size,
                            "pooling_type": pool_type,
                            "strides": pool_stride,
                            "paddings": pool_padding,
                            "global_pooling": global_pooling,
                            "ceil_mode": ceil_mode,
                            "exclusive": exclusive,
                            "data_format": data_format})
    return out


def adaptive_pool2d(input, pool_size, pool_type="avg", name=None):
    helper = LayerHelper("adaptive_pool2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="adaptive_pool2d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pool_size": pool_size,
                            "pooling_type": pool_type})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9,
               epsilon=1e-5, param_attr=None, bias_attr=None,
               data_layout="NCHW", name=None, moving_mean_name=None,
               moving_variance_name=None, use_global_stats=False):
    """Reference: layers/nn.py batch_norm -> batch_norm_op.cc. Running
    mean/var are persistable vars updated in-graph each step (MeanOut
    aliases Mean), matching the reference's in-place update."""
    helper = LayerHelper("batch_norm", name=name, act=act)
    dtype = input.dtype
    c = input.shape[1] if data_layout == "NCHW" and len(input.shape) == 4 \
        else input.shape[-1]
    if len(input.shape) == 2:
        c = input.shape[1]
    scale = helper.create_parameter(attr=param_attr, shape=(c,),
                                    dtype=dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(attr=bias_attr, shape=(c,),
                                   dtype=dtype, is_bias=True)
    mean = _bn_stat(helper, moving_mean_name, c, dtype, 0.0)
    var = _bn_stat(helper, moving_variance_name, c, dtype, 1.0)
    y = helper.create_variable_for_type_inference(dtype)
    saved_mean = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    helper.append_op(
        type="batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [var]},
        outputs={"Y": [y], "MeanOut": [mean], "VarianceOut": [var],
                 "SavedMean": [saved_mean],
                 "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon,
               "is_test": is_test, "data_layout": data_layout,
               "use_global_stats": use_global_stats})
    return helper.append_activation(y)


def _bn_stat(helper, name, c, dtype, init_val):
    """Create a moving-stat persistable var + startup init."""
    from .. import unique_name
    vname = name or unique_name.generate(helper.name + ".moving")
    v = helper.main_program.global_block().create_var(
        name=vname, shape=(c,), dtype=dtype, persistable=True,
        stop_gradient=True)
    sblock = helper.startup_program.global_block()
    sv = sblock.create_var(name=vname, shape=(c,), dtype=dtype,
                           persistable=True, stop_gradient=True)
    Constant(init_val)(sv, sblock)
    return v


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    """Reference: layers/nn.py layer_norm -> layer_norm_op.cc (pallas
    fused variant available, ops/pallas/layer_norm.py)."""
    helper = LayerHelper("layer_norm", name=name, act=act)
    dtype = input.dtype
    nshape = 1
    for d in input.shape[begin_norm_axis:]:
        nshape *= d
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(attr=param_attr, shape=(nshape,),
                                    dtype=dtype,
                                    default_initializer=Constant(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(attr=bias_attr, shape=(nshape,),
                                    dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    y = helper.create_variable_for_type_inference(dtype)
    # the op emits statistics in f32 regardless of input dtype
    mean = helper.create_variable_for_type_inference(
        "float32", stop_gradient=True)
    var = helper.create_variable_for_type_inference(
        "float32", stop_gradient=True)
    helper.append_op(type="layer_norm", inputs=inputs,
                     outputs={"Y": [y], "Mean": [mean],
                              "Variance": [var]},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(y)


def group_norm(input, groups, epsilon=1e-5, param_attr=None,
               bias_attr=None, act=None, name=None):
    helper = LayerHelper("group_norm", name=name, act=act)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        s = helper.create_parameter(attr=param_attr, shape=(c,),
                                    dtype=input.dtype,
                                    default_initializer=Constant(1.0))
        inputs["Scale"] = [s]
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr, shape=(c,),
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    y = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference(input.dtype,
                                                     stop_gradient=True)
    var = helper.create_variable_for_type_inference(input.dtype,
                                                    stop_gradient=True)
    helper.append_op(type="group_norm", inputs=inputs,
                     outputs={"Y": [y], "Mean": [mean],
                              "Variance": [var]},
                     attrs={"groups": groups, "epsilon": epsilon})
    return helper.append_activation(y)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(x.dtype,
                                                     stop_gradient=True)
    helper.append_op(type="dropout", inputs={"X": [x]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"dropout_prob": dropout_prob,
                            "is_test": is_test, "seed": seed or 0,
                            "dropout_implementation":
                                dropout_implementation})
    return out


# ---------------------------------------------------------------------------
# losses / softmax
# ---------------------------------------------------------------------------

def softmax(input, axis=-1, use_cudnn=False, name=None):
    return _simple("softmax", input, {"axis": axis}, name)


def log_softmax(input, axis=-1, name=None):
    return _simple("log_softmax", input, {"axis": axis}, name)


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="cross_entropy",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    helper = LayerHelper("softmax_with_cross_entropy")
    sm = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(type="softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Softmax": [sm], "Loss": [loss]},
                     attrs={"soft_label": soft_label,
                            "ignore_index": ignore_index, "axis": axis})
    if return_softmax:
        return loss, sm
    return loss


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="square_error_cost",
                     inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out]})
    return out


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100,
                                      name=None, normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x], "Label": [label]},
                     outputs={"Out": [out]},
                     attrs={"ignore_index": ignore_index,
                            "normalize": normalize})
    return out


def smooth_l1(x, y, sigma=1.0):
    helper = LayerHelper("smooth_l1_loss")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="smooth_l1_loss",
                     inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs={"sigma": sigma})
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="huber_loss",
                     inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out]}, attrs={"delta": delta})
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    helper = LayerHelper("kldiv_loss", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="kldiv_loss",
                     inputs={"X": [x], "Target": [target]},
                     outputs={"Loss": [out]},
                     attrs={"reduction": reduction})
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="log_loss",
                     inputs={"Predicted": [input], "Labels": [label]},
                     outputs={"Loss": [out]}, attrs={"epsilon": epsilon})
    return out


# ---------------------------------------------------------------------------
# reductions / simple math
# ---------------------------------------------------------------------------

def mean(x, name=None):
    return _simple("mean", x, name=name)


def _reduce(op_type, input, dim, keep_dim, name):
    return _simple(op_type, input,
                   {"dim": dim, "keep_dim": keep_dim,
                    "reduce_all": dim is None}, name)


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_prod", input, dim, keep_dim, name)


def reduce_all(input, dim=None, keep_dim=False, name=None):
    return _simple("reduce_all", input,
                   {"dim": dim, "keep_dim": keep_dim,
                    "reduce_all": dim is None}, name, out_dtype="bool",
                   stop_gradient=True)


def reduce_any(input, dim=None, keep_dim=False, name=None):
    return _simple("reduce_any", input,
                   {"dim": dim, "keep_dim": keep_dim,
                    "reduce_all": dim is None}, name, out_dtype="bool",
                   stop_gradient=True)


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None,
          name=None):
    helper = LayerHelper("scale", name=name, act=act)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="scale", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"scale": scale, "bias": bias,
                            "bias_after_scale": bias_after_scale})
    return helper.append_activation(out)


def _elementwise(op_type, x, y, axis, act, name):
    helper = LayerHelper(op_type, name=name, act=act)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type=op_type, inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return helper.append_activation(out)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_div", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_min", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_max", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_pow", x, y, axis, act, name)


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0,
           name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="matmul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"transpose_x": transpose_x,
                            "transpose_y": transpose_y, "alpha": alpha})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    return out


def clip(x, min, max, name=None):
    return _simple("clip", x, {"min": min, "max": max}, name)


def clip_by_norm(x, max_norm, name=None):
    return _simple("clip_by_norm", x, {"max_norm": max_norm}, name)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    return _simple("norm", x, {"axis": axis, "epsilon": epsilon}, name)


# ---------------------------------------------------------------------------
# shape ops
# ---------------------------------------------------------------------------

def reshape(x, shape, actual_shape=None, act=None, inplace=False,
            name=None):
    helper = LayerHelper("reshape2", name=name, act=act)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="reshape2", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"shape": tuple(shape)})
    return helper.append_activation(out)


def transpose(x, perm, name=None):
    return _simple("transpose2", x, {"axis": tuple(perm)}, name)


def squeeze(input, axes, name=None):
    return _simple("squeeze2", input, {"axes": tuple(axes)}, name)


def unsqueeze(input, axes, name=None):
    return _simple("unsqueeze2", input, {"axes": tuple(axes)}, name)


def flatten(x, axis=1, name=None):
    return _simple("flatten2", x, {"axis": axis}, name)


def expand(x, expand_times, name=None):
    return _simple("expand", x, {"expand_times": tuple(expand_times)},
                   name)


def slice(input, axes, starts, ends):
    return _simple("slice", input,
                   {"axes": tuple(axes), "starts": tuple(starts),
                    "ends": tuple(ends)})


def shape(input):
    return _simple("shape", input, out_dtype="int32", stop_gradient=True)


def stack(x, axis=0):
    helper = LayerHelper("stack")
    xs = x if isinstance(x, (list, tuple)) else [x]
    out = helper.create_variable_for_type_inference(xs[0].dtype)
    helper.append_op(type="stack", inputs={"X": xs},
                     outputs={"Y": [out]}, attrs={"axis": axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    n = num if num is not None else x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype)
            for _ in range(n)]
    helper.append_op(type="unstack", inputs={"X": [x]},
                     outputs={"Y": outs},
                     attrs={"axis": axis, "num": n})
    return outs


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat", name=name)
    out = helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op(type="concat", inputs={"X": input},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def split(input, num_or_sections, dim=0, name=None):
    helper = LayerHelper("split", name=name)
    n = num_or_sections if isinstance(num_or_sections, int) \
        else len(num_or_sections)
    outs = [helper.create_variable_for_type_inference(input.dtype)
            for _ in range(n)]
    helper.append_op(type="split", inputs={"X": [input]},
                     outputs={"Out": outs},
                     attrs={"num_or_sections": num_or_sections,
                            "axis": dim})
    return outs


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="gather",
                     inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]}, attrs={"axis": 0})
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="gather_nd",
                     inputs={"X": [input], "Index": [index]},
                     outputs={"Out": [out]})
    return out


def scatter(input, index, updates, overwrite=True, name=None):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="scatter",
                     inputs={"X": [input], "Ids": [index],
                             "Updates": [updates]},
                     outputs={"Out": [out]},
                     attrs={"overwrite": overwrite})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    return _simple("pad", x, {"paddings": tuple(paddings),
                              "pad_value": pad_value}, name)


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    return _simple("pad2d", input,
                   {"paddings": tuple(paddings), "mode": mode,
                    "pad_value": pad_value, "data_format": data_format},
                   name)


def one_hot(input, depth, allow_out_of_range=False):
    return _simple("one_hot", input, {"depth": depth},
                   out_dtype="float32", stop_gradient=True)


def cast(x, dtype):
    from ..framework import convert_dtype
    return _simple("cast", x, {"dtype": convert_dtype(dtype)},
                   out_dtype=convert_dtype(dtype))


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    vals = helper.create_variable_for_type_inference(input.dtype,
                                                     stop_gradient=True)
    idx = helper.create_variable_for_type_inference("int64",
                                                    stop_gradient=True)
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [vals], "Indices": [idx]},
                     attrs={"k": k})
    return vals, idx


def argsort(input, axis=-1, descending=False, name=None):
    helper = LayerHelper("argsort", name=name)
    vals = helper.create_variable_for_type_inference(input.dtype,
                                                     stop_gradient=True)
    idx = helper.create_variable_for_type_inference("int64",
                                                    stop_gradient=True)
    helper.append_op(type="argsort", inputs={"X": [input]},
                     outputs={"Out": [vals], "Indices": [idx]},
                     attrs={"axis": axis, "descending": descending})
    return vals, idx


def argmax(x, axis=0):
    return _simple("arg_max", x, {"axis": axis}, out_dtype="int64",
                   stop_gradient=True)


def argmin(x, axis=0):
    return _simple("arg_min", x, {"axis": axis}, out_dtype="int64",
                   stop_gradient=True)


def where(condition, x, y):
    helper = LayerHelper("where")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="where",
                     inputs={"Condition": [condition], "X": [x],
                             "Y": [y]},
                     outputs={"Out": [out]})
    return out


def cumsum(x, axis=-1, exclusive=False, reverse=False):
    return _simple("cumsum", x, {"axis": axis, "exclusive": exclusive,
                                 "reverse": reverse})


def sequence_mask(x, maxlen, dtype="float32", name=None):
    return _simple("sequence_mask", x, {"maxlen": maxlen, "dtype": dtype},
                   name, out_dtype=dtype, stop_gradient=True)


def resize_bilinear(input, out_shape, name=None, align_corners=True):
    return _simple("interpolate", input,
                   {"out_shape": tuple(out_shape), "method": "bilinear",
                    "align_corners": align_corners}, name)


def resize_nearest(input, out_shape, name=None, align_corners=True):
    return _simple("interpolate", input,
                   {"out_shape": tuple(out_shape), "method": "nearest",
                    "align_corners": align_corners}, name)


def pixel_shuffle(x, upscale_factor):
    return _simple("pixel_shuffle", x,
                   {"upscale_factor": upscale_factor})


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="grid_sampler",
                     inputs={"X": [x], "Grid": [grid]},
                     outputs={"Output": [out]})
    return out


def maxout(x, groups, name=None, axis=1):
    return _simple("maxout", x, {"groups": groups, "axis": axis}, name)


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment")
    if in_place:
        out = x
    else:
        out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="increment", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"step": value})
    return out


def fsp_matrix(x, y):
    """Flow-of-solution-procedure matrix between two feature maps of
    the same spatial size (reference: layers/nn.py fsp_matrix ->
    operators/fsp_op.cc); used by the FSP distiller."""
    helper = LayerHelper("fsp_matrix")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="fsp_matrix", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    """Reference: layers/nn.py label_smooth -> label_smooth_op.cc."""
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(type="label_smooth", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"epsilon": float(epsilon)})
    return out


def scaled_dot_product_attention(q, k, v, bias=None, scale=1.0,
                                 dropout_rate=0.0, causal=False,
                                 is_test=False, window=0, name=None,
                                 num_heads=None):
    """Fused attention core: softmax(q @ k^T * scale + bias) @ v, with
    optional in-kernel attention dropout and causal masking. Two input
    layouts: [batch, heads, seq, head_dim], or, with ``num_heads``,
    [batch, seq, heads * head_dim] as a projection produces it and the
    output projection reads it (no head split or merge to build: the
    flash pair picks the heads inside the kernel); the output has
    ``q``'s shape. Lowers to one fused op (pallas flash kernel —
    blocked online softmax, recompute backward — when
    FLAGS_op_library=pallas; XLA-fused composite otherwise). ``bias`` is
    an additive attention *mask* (non-differentiable), broadcastable to
    [batch, 1 or heads, q_len, k_len] in either layout; add a trainable
    bias with elementwise_add instead. ``k`` and ``v`` may have fewer
    heads than ``q`` (grouped queries: q head i reads kv head
    i // (heads // kv heads)); ``window`` > 0, with ``causal``, lets
    row i read keys i-window+1..i only. In rank 4 ``v`` (and the
    output) may have another head width than ``q`` and ``k`` (latent
    attention: 192-wide keys beside 128-wide values). See
    ops/pallas/attention.py."""
    if len(q.shape) == 3 and k.shape[-1] != v.shape[-1]:
        raise ValueError(
            "scaled_dot_product_attention: values of another width "
            "than the keys (%r beside %r) want rank 4 "
            "[batch, heads, seq, head_dim] inputs"
            % (v.shape[-1], k.shape[-1]))
    if (len(q.shape) == 3) != bool(num_heads):
        raise ValueError(
            "scaled_dot_product_attention: num_heads goes with rank-3 "
            "[batch, seq, heads * head_dim] inputs and with nothing "
            "else; got rank %d and num_heads=%r"
            % (len(q.shape), num_heads))
    helper = LayerHelper("sdpa", name=name)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        inputs["Bias"] = [bias]
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op(type="scaled_dot_product_attention",
                     inputs=inputs, outputs={"Out": [out]},
                     attrs={"scale": float(scale),
                            "dropout_rate": float(dropout_rate),
                            "causal": bool(causal),
                            "is_test": bool(is_test),
                            "window": int(window),
                            "num_heads": int(num_heads or 0)})
    return out


def rms_norm(input, epsilon=1e-5, param_attr=None, name=None):
    """RMSNorm over the last axis with a weight (ops/nn_ops.py
    rms_norm): ``x / sqrt(mean(x^2) + epsilon) * w``; the weight is
    ``<name>.w_0``, ones by default."""
    helper = LayerHelper("rms_norm", name=name)
    w = helper.create_parameter(attr=param_attr,
                                shape=(int(input.shape[-1]),),
                                dtype=input.dtype,
                                default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="rms_norm", inputs={"X": [input], "Scale": [w]},
                     outputs={"Y": [out]},
                     attrs={"epsilon": float(epsilon)})
    return out


def rotary_embedding(x, theta=10000.0, start=0, width=None,
                     interleaved=False, name=None):
    """Rotary position embedding of [batch, heads, seq, head_dim]; row
    s is position s. By default the whole head in the rotate-half
    layout; ``start`` / ``width`` turn lanes ``[start, start + width)``
    alone and pass the others through (a decoupled rotary part beside
    plain lanes), ``interleaved`` pairs lanes (2i, 2i + 1) of the part
    in place of (i, i + width/2)."""
    return _simple("rotary_embedding", x,
                   {"theta": float(theta), "start": int(start),
                    "width": int(width or 0),
                    "interleaved": bool(interleaved)}, name=name)


def moe_ffn(x, num_experts, d_ffn, capacity_factor=1.25, top_k=1,
            param_attr=None, name=None):
    """Mixture-of-experts FFN layer over ``[tokens, d_model]`` input:
    Switch (top_k=1) / GShard top-2 routing into ``num_experts``
    relu-FFN experts of width ``d_ffn`` (parallel/moe.py). Returns
    ``(out [tokens, d_model], aux_loss scalar)`` — add the aux loss
    (scaled) into the training objective to regularize routing.

    Under a CompiledProgram mesh with an ``ep`` axis the op runs
    expert-parallel: expert weights shard over ``ep`` on their leading
    E dim, tokens data-shard over the same axis, and one capacity-
    bucketed ``all_to_all`` each way moves only the dispatched tokens
    across ICI. Without an ep axis it is the exact single-device
    reference — the same program serves both, like the attention ops."""
    helper = LayerHelper("moe_ffn", name=name)
    enforce(x.shape is not None and len(x.shape) == 2,
            "moe_ffn wants [tokens, d_model] input (flatten sequence "
            "dims first), got shape %r" % (x.shape,))
    d_model = int(x.shape[1])
    E, F = int(num_experts), int(d_ffn)
    gate_w = helper.create_parameter(attr=param_attr,
                                     shape=(d_model, E), dtype=x.dtype)
    w1 = helper.create_parameter(attr=param_attr, shape=(E, d_model, F),
                                 dtype=x.dtype)
    b1 = helper.create_parameter(attr=param_attr, shape=(E, F),
                                 dtype=x.dtype, is_bias=True)
    w2 = helper.create_parameter(attr=param_attr, shape=(E, F, d_model),
                                 dtype=x.dtype)
    b2 = helper.create_parameter(attr=param_attr, shape=(E, d_model),
                                 dtype=x.dtype, is_bias=True)
    # expert weights shard over ep on the leading E axis; the mesh-less
    # case ignores the annotation (PartitionSpec axes not in the mesh
    # never bind)
    from ..parallel.api import shard as _shard
    _shard(w1, "ep", None, None)
    _shard(b1, "ep", None)
    _shard(w2, "ep", None, None)
    _shard(b2, "ep", None)
    out = helper.create_variable_for_type_inference(x.dtype)
    aux = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="moe_ffn",
                     inputs={"X": [x], "GateW": [gate_w], "W1": [w1],
                             "B1": [b1], "W2": [w2], "B2": [b2]},
                     outputs={"Out": [out], "AuxLoss": [aux]},
                     attrs={"capacity_factor": float(capacity_factor),
                            "top_k": int(top_k)})
    return out, aux


def _step_counters(helper, lib):
    """The one persistable that a program's ops of one kind add their
    counts to (``lib.COUNTERS_VAR``, a float32 a name of
    ``lib.COUNTER_NAMES``), zeroed by the startup program."""
    block = helper.main_program.global_block()
    if lib.COUNTERS_VAR in block.vars:
        return block.vars[lib.COUNTERS_VAR]
    from .tensor import create_global_var
    return create_global_var((len(lib.COUNTER_NAMES),), 0.0, "float32",
                             persistable=True, name=lib.COUNTERS_VAR)


def _moe_counters(helper):
    """The held-experts ops' (parallel/moe.py COUNTERS_VAR)."""
    from ..parallel import moe
    return _step_counters(helper, moe)


def moe_sigmoid_router(x, num_experts, top_k, route_scale=1.0,
                       route_norm=True, balance_coeff=0.0,
                       first_held=0, num_held=0, param_attr=None,
                       name=None):
    """Sigmoid top-k router over ``num_experts`` (the published width)
    for ``[tokens, d_model]`` input (parallel/moe.py
    sigmoid_topk_route): scores in float32, the ``top_k`` chosen on
    score plus a bias BUFFER (``<name>.bias``, persistable, not a
    parameter; moved after each step by ``balance_coeff`` towards the
    mean load, then centred), the chosen scores renormalised
    (``route_norm``) and times ``route_scale``. ``first_held`` /
    ``num_held`` only say whose loads the step's counters record.
    Returns ``(idx [tokens, top_k] int32, weight [tokens, top_k]
    float32)`` for ``moe_held_experts``."""
    helper = LayerHelper("moe_router", name=name)
    enforce(x.shape is not None and len(x.shape) == 2,
            "moe_sigmoid_router wants [tokens, d_model] input, got "
            "shape %r" % (x.shape,))
    E = int(num_experts)
    w = helper.create_parameter(attr=param_attr,
                                shape=(int(x.shape[1]), E), dtype=x.dtype)
    from .tensor import create_global_var
    bias = create_global_var((E,), 0.0, "float32", persistable=True,
                             name=helper.name + ".bias")
    counters = _moe_counters(helper)
    idx = helper.create_variable_for_type_inference(
        "int32", stop_gradient=True)
    weight = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="moe_sigmoid_router",
        inputs={"X": [x], "W": [w], "Bias": [bias],
                "Counters": [counters]},
        outputs={"TopkIdx": [idx], "TopkWeight": [weight],
                 "BiasOut": [bias], "CountersOut": [counters]},
        attrs={"top_k": int(top_k), "route_scale": float(route_scale),
               "route_norm": bool(route_norm),
               "balance_coeff": float(balance_coeff),
               "first_held": int(first_held), "n_held": int(num_held)})
    return idx, weight


def moe_held_experts(x, idx, weight, num_held, d_ffn, first_held=0,
                     row_capacity=None, param_attr=None, name=None):
    """The part of a top-k expert layer that THIS chip's experts give
    (parallel/moe.py held_experts_ffn): experts ``first_held ..
    first_held + num_held - 1`` of the router's width, gated-SiLU MLPs
    of width ``d_ffn`` (``<name>.w_gate`` / ``.w_up`` [num_held,
    d_model, d_ffn], ``.w_down`` [num_held, d_ffn, d_model]), over the
    tokens routed to them: sorted by expert into a static buffer of
    ``row_capacity`` rows (default: every assignment, so nothing can
    overflow) walked in chunks up to the rows in use, three grouped
    matrix products a chunk, scatter-added back times ``weight``. An assignment past the buffer makes the output NaN and
    is counted (``telemetry()["moe"]``); nothing is dropped in
    silence."""
    helper = LayerHelper("moe_experts", name=name)
    enforce(x.shape is not None and len(x.shape) == 2,
            "moe_held_experts wants [tokens, d_model] input, got "
            "shape %r" % (x.shape,))
    d, n, f = int(x.shape[1]), int(num_held), int(d_ffn)
    from ..param_attr import ParamAttr

    def param(suffix, shape):
        attr = ParamAttr._to_attr(param_attr)
        attr = ParamAttr(name=helper.name + suffix,
                         initializer=attr.initializer)
        return helper.create_parameter(attr=attr, shape=shape,
                                       dtype=x.dtype)

    w_gate = param(".w_gate", (n, d, f))
    w_up = param(".w_up", (n, d, f))
    w_down = param(".w_down", (n, f, d))
    counters = _moe_counters(helper)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="moe_held_experts",
        inputs={"X": [x], "TopkIdx": [idx], "Weight": [weight],
                "WGate": [w_gate], "WUp": [w_up], "WDown": [w_down],
                "Counters": [counters]},
        outputs={"Out": [out], "CountersOut": [counters]},
        attrs={"first_held": int(first_held),
               "row_capacity": int(row_capacity or 0)})
    return out


def short_conv(input, kernel_size=4, param_attr=None, name=None):
    """Causal depthwise convolution over the positions of
    ``[batch, seq, channels]``, then SiLU (ops/kda_ops.py short_conv):
    channel c of row t reads rows t-kernel_size+1..t of channel c
    alone, through ``<name>.w_0`` [channels, kernel_size] (no bias)."""
    helper = LayerHelper("short_conv", name=name)
    enforce(input.shape is not None and len(input.shape) == 3,
            "short_conv wants [batch, seq, channels] input, got shape "
            "%r" % (input.shape,))
    w = helper.create_parameter(
        attr=param_attr, shape=(int(input.shape[-1]), int(kernel_size)),
        dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="short_conv", inputs={"X": [input], "W": [w]},
                     outputs={"Out": [out]})
    return out


def gated_rms_norm(input, gate, group_size, epsilon=1e-5, param_attr=None,
                   name=None):
    """RMSNorm over each group of ``group_size`` lanes of the last axis
    (a head) with one weight ``<name>.w_0`` [group_size], times
    ``sigmoid(gate)`` (ops/kda_ops.py gated_rms_norm)."""
    helper = LayerHelper("gated_rms_norm", name=name)
    w = helper.create_parameter(attr=param_attr,
                                shape=(int(group_size),),
                                dtype=input.dtype,
                                default_initializer=Constant(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="gated_rms_norm",
                     inputs={"X": [input], "Gate": [gate], "Scale": [w]},
                     outputs={"Y": [out]},
                     attrs={"epsilon": float(epsilon)})
    return out


def kda_gate(x, a_log, dt_bias):
    """The per-channel log decay of Kimi Delta Attention (ops/kda_ops.py
    kda_gate): ``-exp(a_log_h) * softplus(x + dt_bias)`` in float32 for
    x [batch, seq, heads * head_dim], ``a_log`` [heads] one scalar a
    head and ``dt_bias`` [heads * head_dim]."""
    helper = LayerHelper("kda_gate")
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(type="kda_gate",
                     inputs={"X": [x], "ALog": [a_log],
                             "DtBias": [dt_bias]},
                     outputs={"Out": [out]})
    return out


def kda_attention(q, k, v, g, beta, scale=1.0, name=None):
    """The core of Kimi Delta Attention (ops/kda_ops.py): a gated
    delta-rule linear attention with a per-channel decay. ``q``, ``k``
    [batch, seq, heads * dk], ``v`` [batch, seq, heads * dv], the log
    decay ``g`` (float32, <= 0, ``kda_gate``) of k's shape and ``beta``
    [batch, seq, heads] in (0, 1); a head's state ``S`` [dk, dv] starts
    at nought at the row's first position and
    ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
    v_t^T``, ``o_t = S_t^T q_t``, with q and k L2-normalised per head
    and q times ``scale``. Runs chunk-parallel; the backward pass keeps
    these inputs alone. Returns [batch, seq, heads * dv] in v's type."""
    helper = LayerHelper("kda", name=name)
    from ..ops import kda_ops
    counters = _step_counters(helper, kda_ops)
    out = helper.create_variable_for_type_inference(v.dtype)
    helper.append_op(
        type="kda_attention",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta],
                "Counters": [counters]},
        outputs={"Out": [out], "CountersOut": [counters]},
        attrs={"scale": float(scale)})
    return out


# ---------------------------------------------------------------------------
# sequence-labeling / sampled losses (reference: layers/nn.py warpctc,
# edit_distance, linear_chain_crf, crf_decoding, nce, hsigmoid,
# sampled_softmax_with_cross_entropy, rank_loss, bpr_loss, cos_sim)
# ---------------------------------------------------------------------------

def warpctc(input, label, blank=0, norm_by_times=False,
            input_length=None, label_length=None):
    """CTC loss (reference: layers/nn.py warpctc -> warpctc_op.cc).
    Padded redesign: input [B, T, C] with input_length, label [B, L]
    with label_length (the LoD form has no padded equivalent)."""
    enforce(input_length is not None and label_length is not None,
            "padded CTC needs input_length and label_length")
    helper = LayerHelper("warpctc")
    loss = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="warpctc",
        inputs={"Logits": [input], "Label": [label],
                "LogitsLength": [input_length],
                "LabelLength": [label_length]},
        outputs={"Loss": [loss]},
        attrs={"blank": blank, "norm_by_times": norm_by_times})
    return loss


def ctc_greedy_decoder(input, blank, input_length=None):
    """Greedy CTC decode: argmax per frame, collapse repeats, strip
    blanks (reference: layers/nn.py ctc_greedy_decoder)."""
    helper = LayerHelper("ctc_align")
    ids = argmax(input, axis=-1)
    out = helper.create_variable_for_type_inference("int32")
    out_len = helper.create_variable_for_type_inference("int32")
    if input_length is None:
        from . import tensor as _t
        input_length = _t.fill_constant_batch_size_like(
            input, shape=[-1, 1], dtype="int64",
            value=input.shape[1] if len(input.shape) > 1 else 1)
    helper.append_op(
        type="ctc_align",
        inputs={"Input": [ids], "InputLength": [input_length]},
        outputs={"Output": [out], "OutputLength": [out_len]},
        attrs={"blank": blank, "merge_repeated": True})
    return out, out_len


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  input_length=None, label_length=None):
    """Levenshtein distance (reference: layers/nn.py edit_distance)."""
    helper = LayerHelper("edit_distance")
    out = helper.create_variable_for_type_inference("float32")
    seq_num = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="edit_distance",
        inputs={"Hyps": [input], "Refs": [label],
                "HypsLength": [input_length],
                "RefsLength": [label_length]},
        outputs={"Out": [out], "SequenceNum": [seq_num]},
        attrs={"normalized": normalized})
    return out, seq_num


def linear_chain_crf(input, label, param_attr=None, length=None):
    """CRF log-likelihood; creates the [D+2, D] transition parameter
    (rows: start, stop, transitions — reference layout,
    linear_chain_crf_op.h)."""
    helper = LayerHelper("linear_chain_crf")
    size = input.shape[-1]
    transition = helper.create_parameter(
        attr=param_attr, shape=(size + 2, size), dtype=input.dtype)
    ll = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="linear_chain_crf",
        inputs={"Emission": [input], "Transition": [transition],
                "Label": [label], "Length": [length]},
        outputs={"LogLikelihood": [ll]})
    return ll


def crf_decoding(input, param_attr, label=None, length=None):
    """Viterbi decode using a trained transition param (reference:
    layers/nn.py crf_decoding). ``param_attr`` may be the transition
    Variable itself or its ParamAttr/name.

    Reference semantics for ``label``: when given, the output is a 0/1
    CORRECTNESS mask (1 where the decoded tag differs from the label —
    crf_decoding_op.h sets output to the mismatch indicator) rather
    than the path itself."""
    helper = LayerHelper("crf_decoding")
    from ..framework import Variable as _Var
    if isinstance(param_attr, _Var):
        transition = param_attr
    else:
        name = getattr(param_attr, "name", param_attr)
        transition = helper.main_program.global_block().var(name)
    path = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="crf_decoding",
        inputs={"Emission": [input], "Transition": [transition],
                "Length": [length]},
        outputs={"ViterbiPath": [path]})
    if label is not None:
        from .control_flow import not_equal
        from .tensor import cast
        return cast(not_equal(path, label), "int64")
    return path


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=None,
        name=None, sampler="uniform", custom_dist=None, seed=0,
        is_sparse=False):
    """Noise-contrastive estimation loss; creates the class weight and
    bias (reference: layers/nn.py nce -> nce_op.cc)."""
    if sample_weight is not None:
        from ..core.enforce import UnimplementedError
        raise UnimplementedError(
            "NCE sample_weight is not supported (the nce op weights "
            "every example equally); weight the returned per-example "
            "cost instead")
    helper = LayerHelper("nce", name=name)
    dim = input.shape[-1]
    w = helper.create_parameter(attr=param_attr,
                                shape=(num_total_classes, dim),
                                dtype=input.dtype)
    b = None
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr,
                                    shape=(num_total_classes,),
                                    dtype=input.dtype, is_bias=True)
    cost = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="nce",
        inputs={"Input": [input], "Weight": [w],
                "Bias": [b] if b is not None else [],
                "Label": [label]},
        outputs={"Cost": [cost]},
        attrs={"num_total_classes": num_total_classes,
               "num_neg_samples": num_neg_samples or 10,
               "seed": seed})
    return cost


def hsigmoid(input, label, num_classes, param_attr=None,
             bias_attr=None, name=None):
    """Hierarchical sigmoid over the default complete binary tree
    (reference: layers/nn.py hsigmoid)."""
    helper = LayerHelper("hierarchical_sigmoid", name=name)
    dim = input.shape[-1]
    w = helper.create_parameter(attr=param_attr,
                                shape=(num_classes - 1, dim),
                                dtype=input.dtype)
    b = None
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr,
                                    shape=(num_classes - 1,),
                                    dtype=input.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    pre_out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="hierarchical_sigmoid",
        inputs={"X": [input], "W": [w],
                "Bias": [b] if b is not None else [],
                "Label": [label]},
        outputs={"Out": [out], "PreOut": [pre_out]},
        attrs={"num_classes": num_classes})
    return out


def sampled_softmax_with_cross_entropy(logits, label, num_samples,
                                       num_true=1, seed=0):
    """Sampled softmax (reference: layers/nn.py
    sampled_softmax_with_cross_entropy -> sample_logits_op.cc +
    softmax_with_cross_entropy)."""
    helper = LayerHelper("sample_logits")
    sampled = helper.create_variable_for_type_inference(logits.dtype)
    new_label = helper.create_variable_for_type_inference("int64")
    samples = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="sample_logits",
        inputs={"Logits": [logits], "Labels": [label]},
        outputs={"SampledLogits": [sampled],
                 "SampledLabels": [new_label], "Samples": [samples]},
        attrs={"num_samples": num_samples, "seed": seed})
    loss = helper.create_variable_for_type_inference(logits.dtype)
    softmax = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [sampled], "Label": [new_label]},
        outputs={"Loss": [loss], "Softmax": [softmax]},
        attrs={"soft_label": False})
    return loss


def rank_loss(label, left, right, name=None):
    helper = LayerHelper("rank_loss", name=name)
    out = helper.create_variable_for_type_inference(left.dtype)
    helper.append_op(type="rank_loss",
                     inputs={"Label": [label], "Left": [left],
                             "Right": [right]},
                     outputs={"Out": [out]})
    return out


def bpr_loss(input, label, name=None):
    helper = LayerHelper("bpr_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="bpr_loss",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Out": [out]})
    return out


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim")
    out = helper.create_variable_for_type_inference(X.dtype)
    xn = helper.create_variable_for_type_inference(X.dtype)
    yn = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op(type="cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn],
                              "YNorm": [yn]})
    return out


# ---------------------------------------------------------------------------
# vision layers (reference: layers/nn.py lrn, affine_channel, pool3d,
# spectral_norm, row_conv, bilinear_tensor_product, temporal_shift,
# shuffle_channel, space_to_depth, crop, pad_constant_like, multiplex,
# image resize aliases)
# ---------------------------------------------------------------------------

def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="lrn", inputs={"X": [input]},
                     outputs={"Out": [out], "MidOut": [mid]},
                     attrs={"n": n, "k": k, "alpha": alpha,
                            "beta": beta})
    return out


def affine_channel(x, scale=None, bias=None, data_layout="NCHW",
                   name=None):
    helper = LayerHelper("affine_channel", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="affine_channel",
                     inputs={"X": [x], "Scale": [scale],
                             "Bias": [bias]},
                     outputs={"Out": [out]},
                     attrs={"data_layout": data_layout})
    return out


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, ceil_mode=False,
           name=None, exclusive=True):
    helper = LayerHelper("pool3d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)

    def _3(v):
        return tuple(v) if isinstance(v, (list, tuple)) else (v,) * 3

    helper.append_op(type="pool3d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"ksize": _3(pool_size),
                            "pooling_type": pool_type,
                            "strides": _3(pool_stride),
                            "paddings": _3(pool_padding),
                            "global_pooling": global_pooling,
                            "ceil_mode": ceil_mode,
                            "exclusive": exclusive})
    return out


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    """Creates the persistable u/v power-iteration vectors (reference:
    layers/nn.py spectral_norm)."""
    helper = LayerHelper("spectral_norm", name=name)
    h = weight.shape[dim]
    w_rest = 1
    for i, d in enumerate(weight.shape):
        if i != dim:
            w_rest *= d
    from ..initializer import Normal
    u = helper.create_parameter(attr=None, shape=(h,),
                                dtype=weight.dtype,
                                default_initializer=Normal(0, 1))
    v = helper.create_parameter(attr=None, shape=(w_rest,),
                                dtype=weight.dtype,
                                default_initializer=Normal(0, 1))
    u.stop_gradient = True
    v.stop_gradient = True
    out = helper.create_variable_for_type_inference(weight.dtype)
    helper.append_op(type="spectral_norm",
                     inputs={"Weight": [weight], "U": [u], "V": [v]},
                     outputs={"Out": [out]},
                     attrs={"dim": dim, "power_iters": power_iters,
                            "eps": eps})
    return out


def row_conv(input, future_context_size, param_attr=None,
             act=None):
    helper = LayerHelper("row_conv", act=act)
    filt = helper.create_parameter(
        attr=param_attr, shape=(future_context_size + 1,
                                input.shape[-1]),
        dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="row_conv",
                     inputs={"X": [input], "Filter": [filt]},
                     outputs={"Out": [out]})
    return helper.append_activation(out)


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    helper = LayerHelper("bilinear_tensor_product", name=name, act=act)
    w = helper.create_parameter(
        attr=param_attr, shape=(size, x.shape[-1], y.shape[-1]),
        dtype=x.dtype)
    b = None
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr, shape=(1, size),
                                    dtype=x.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="bilinear_tensor_product",
                     inputs={"X": [x], "Y": [y], "Weight": [w],
                             "Bias": [b] if b is not None else []},
                     outputs={"Out": [out]})
    return helper.append_activation(out)


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    helper = LayerHelper("temporal_shift", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="temporal_shift", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"seg_num": seg_num,
                            "shift_ratio": shift_ratio})
    return out


def shuffle_channel(x, group, name=None):
    helper = LayerHelper("shuffle_channel", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="shuffle_channel", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"group": group})
    return out


def space_to_depth(x, blocksize, name=None):
    helper = LayerHelper("space_to_depth", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="space_to_depth", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"blocksize": blocksize})
    return out


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper("crop", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="crop", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"shape": tuple(shape),
                            "offsets_attr": tuple(offsets or
                                                  [0] * len(shape))})
    return out


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper("pad_constant_like", name=name)
    out = helper.create_variable_for_type_inference(y.dtype)
    helper.append_op(type="pad_constant_like",
                     inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"pad_value": pad_value})
    return out


def multiplex(inputs, index):
    helper = LayerHelper("multiplex")
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op(type="multiplex",
                     inputs={"Ids": [index], "X": list(inputs)},
                     outputs={"Out": [out]})
    return out


def mean_iou(input, label, num_classes):
    helper = LayerHelper("mean_iou")
    miou = helper.create_variable_for_type_inference("float32")
    wrong = helper.create_variable_for_type_inference("int32")
    correct = helper.create_variable_for_type_inference("int32")
    helper.append_op(type="mean_iou",
                     inputs={"Predictions": [input],
                             "Labels": [label]},
                     outputs={"OutMeanIou": [miou],
                              "OutWrong": [wrong],
                              "OutCorrect": [correct]},
                     attrs={"num_classes": num_classes})
    return miou, wrong, correct


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    """Image patches -> sequence (reference: layers/nn.py im2sequence
    -> im2sequence_op.cc)."""
    helper = LayerHelper("im2sequence", name=name)

    def _pair(v):
        return tuple(v) if isinstance(v, (list, tuple)) else (v, v)

    pad = padding if isinstance(padding, (list, tuple)) and \
        len(padding) == 4 else _pair(padding) * 2
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="im2sequence", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"kernels": _pair(filter_size),
                            "strides": _pair(stride),
                            "paddings": tuple(pad)})
    return out


def py_func(func, x, out, backward_func=None,
            skip_vars_in_backward_input=None):
    """Run a user Python callable as an op (reference: layers/nn.py
    py_func -> py_func_op.cc). ``out`` vars must be pre-created with
    shapes/dtypes (create_variable); ``backward_func(*inputs,
    *outputs, *output_grads)`` returns input grads (None entries for
    non-differentiable inputs). Under jit the call lowers to a host
    callback (jax.pure_callback)."""
    if skip_vars_in_backward_input is not None:
        from ..core.enforce import UnimplementedError
        raise UnimplementedError(
            "py_func skip_vars_in_backward_input is not supported: "
            "backward_func always receives (*inputs, *outputs, "
            "*output_grads) positionally — drop unused parameters in "
            "the callable instead")
    from ..ops.py_func_op import register_py_func
    helper = LayerHelper("py_func")
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    fid = register_py_func(func, backward_func)
    helper.append_op(
        type="py_func", inputs={"X": list(xs)},
        outputs={"Out": list(outs)},
        attrs={"func_id": fid,
               "out_shapes": tuple(tuple(int(d) for d in o.shape)
                                   for o in outs),
               "out_dtypes": tuple(o.dtype for o in outs)})
    return out


def tree_conv(nodes_vector, edge_set, output_size, num_filters=1,
              max_depth=2, act="tanh", param_attr=None,
              bias_attr=None, name=None):
    """Tree-based convolution (reference: layers/nn.py tree_conv ->
    tree_conv_op.cc). nodes_vector [B, N, F], edge_set [B, E, 2]."""
    helper = LayerHelper("tree_conv", name=name)
    F = nodes_vector.shape[-1]
    w = helper.create_parameter(
        attr=param_attr, shape=(F, 3, output_size, num_filters),
        dtype=nodes_vector.dtype)
    out = helper.create_variable_for_type_inference(
        nodes_vector.dtype)
    helper.append_op(
        type="tree_conv",
        inputs={"NodesVector": [nodes_vector], "EdgeSet": [edge_set],
                "Filter": [w]},
        outputs={"Out": [out]}, attrs={"max_depth": max_depth})
    if bias_attr is not False:
        b = helper.create_parameter(
            attr=bias_attr, shape=(1, 1, output_size, num_filters),
            dtype=nodes_vector.dtype, is_bias=True)
        out = helper.append_bias_op(out, b, axis=-1)
    if act:
        out = _simple(act, out)
    return out


# -- reference API-parity batch (round 3) -----------------------------------

def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _simple("brelu", x, {"t_min": t_min, "t_max": t_max},
                   name=name)


def soft_relu(x, threshold=40.0, name=None):
    return _simple("soft_relu", x, {"threshold": threshold}, name=name)


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return _simple("stanh", x, {"scale_a": scale_a,
                                "scale_b": scale_b}, name=name)


def selu(x, scale=None, alpha=None, name=None):
    attrs = {}
    if scale is not None:
        attrs["scale"] = scale
    if alpha is not None:
        attrs["alpha"] = alpha
    return _simple("selu", x, attrs, name=name)


def adaptive_pool3d(input, pool_size, pool_type="avg", name=None):
    return _simple("adaptive_pool3d", input,
                   {"pool_size": pool_size, "pooling_type": pool_type},
                   name=name)


def conv3d_transpose(input, num_filters, filter_size, padding=0,
                     stride=1, dilation=1, groups=1, param_attr=None,
                     bias_attr=None, act=None, name=None):
    """Reference: layers/nn.py conv3d_transpose ->
    conv_transpose_op.cc (3-D)."""
    helper = LayerHelper("conv3d_transpose", name=name, act=act)
    c_in = input.shape[1]
    fs = filter_size if isinstance(filter_size, (list, tuple)) \
        else (filter_size,) * 3
    w = helper.create_parameter(
        attr=param_attr, shape=(c_in, num_filters // groups) + tuple(fs),
        dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="conv3d_transpose",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": stride, "paddings": padding,
                            "dilations": dilation, "groups": groups})
    if bias_attr is not False:
        b = helper.create_parameter(attr=bias_attr,
                                    shape=(num_filters,),
                                    dtype=input.dtype, is_bias=True)
        out = helper.append_bias_op(out, b, axis=1)
    return helper.append_activation(out)


def dice_loss(input, label, epsilon=1e-5):
    return _simple("dice_loss", input, {"epsilon": epsilon},
                   extra_inputs={"Label": [label]})


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    helper = LayerHelper("npair_loss")
    out = helper.create_variable_for_type_inference(anchor.dtype)
    helper.append_op(type="npair_loss",
                     inputs={"Anchor": [anchor],
                             "Positive": [positive],
                             "Labels": [labels]},
                     outputs={"Out": [out]},
                     attrs={"l2_reg": l2_reg})
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper("margin_rank_loss", name=name)
    out = helper.create_variable_for_type_inference(left.dtype)
    helper.append_op(type="margin_rank_loss",
                     inputs={"X1": [left], "X2": [right],
                             "Label": [label]},
                     outputs={"Out": [out]},
                     attrs={"margin": margin})
    return out


def teacher_student_sigmoid_loss(input, label,
                                 soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    helper = LayerHelper("teacher_student_sigmoid_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="teacher_student_sigmoid_loss",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_max_up_bound": soft_max_up_bound,
                            "soft_max_lower_bound":
                                soft_max_lower_bound})
    return out


def similarity_focus(input, axis, indexes, name=None):
    return _simple("similarity_focus", input,
                   {"axis": axis, "indexes": tuple(indexes)},
                   name=name, stop_gradient=True)


def continuous_value_model(input, cvm, use_cvm=True):
    """Reference: layers/nn.py continuous_value_model -> cvm op."""
    helper = LayerHelper("cvm")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="cvm",
                     inputs={"X": [input], "CVM": [cvm]},
                     outputs={"Y": [out]},
                     attrs={"use_cvm": use_cvm})
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="int64"):
    helper = LayerHelper("sampling_id")
    out = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    helper.append_op(type="sampling_id", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"min": min, "max": max, "seed": seed})
    return out


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    """Reference: layers/nn.py data_norm -> data_norm_op.cc (CTR
    normalization with learned batch statistics)."""
    helper = LayerHelper("data_norm", name=name, act=act)
    c = input.shape[-1]
    size = helper.create_parameter(
        attr=param_attr, shape=(c,), dtype=input.dtype,
        default_initializer=Constant(1.0))
    sum_ = helper.create_parameter(
        attr=param_attr, shape=(c,), dtype=input.dtype,
        default_initializer=Constant(0.0))
    sqsum = helper.create_parameter(
        attr=param_attr, shape=(c,), dtype=input.dtype,
        default_initializer=Constant(1e-4))
    out = helper.create_variable_for_type_inference(input.dtype)
    means = helper.create_variable_for_type_inference(input.dtype)
    scales = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="data_norm",
        inputs={"X": [input], "BatchSize": [size],
                "BatchSum": [sum_], "BatchSquareSum": [sqsum]},
        outputs={"Y": [out], "Means": [means], "Scales": [scales]},
        attrs={"epsilon": epsilon})
    return helper.append_activation(out)


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", actual_shape=None, align_corners=True,
                 align_mode=1):
    """Reference: layers/nn.py image_resize -> interpolate ops."""
    enforce(resample in ("BILINEAR", "NEAREST"),
            "resample must be BILINEAR or NEAREST")
    if out_shape is None:
        enforce(scale is not None, "need out_shape or scale")
        h, w = input.shape[2], input.shape[3]
        out_shape = (int(h * scale), int(w * scale))
    op = "bilinear_interp" if resample == "BILINEAR" \
        else "nearest_interp"
    return _simple(op, input,
                   {"out_h": int(out_shape[0]),
                    "out_w": int(out_shape[1]),
                    "align_corners": align_corners,
                    "align_mode": align_mode}, name=name)


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    h, w = input.shape[2], input.shape[3]
    if h < w:
        oh, ow = out_short_len, int(w * out_short_len / h)
    else:
        oh, ow = int(h * out_short_len / w), out_short_len
    return image_resize(input, out_shape=(oh, ow), resample=resample)


def random_crop(x, shape, seed=None):
    from . import tensor as _t
    helper = LayerHelper("random_crop")
    if seed is None or isinstance(seed, int):
        seed_var = _t.fill_constant((1,), "int64", seed or 0)
    else:
        seed_var = seed
    out = helper.create_variable_for_type_inference(x.dtype)
    seed_out = helper.create_variable_for_type_inference(
        "int64", stop_gradient=True)
    helper.append_op(type="random_crop",
                     inputs={"X": [x], "Seed": [seed_var]},
                     outputs={"Out": [out], "SeedOut": [seed_out]},
                     attrs={"shape": tuple(shape)})
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    helper.append_op(type="gaussian_random", outputs={"Out": [out]},
                     attrs={"shape": tuple(shape), "mean": mean,
                            "std": std, "dtype": dtype})
    return out


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0,
                                    std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random_batch_size_like")
    out = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    helper.append_op(type="gaussian_random_batch_size_like",
                     inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs={"shape": tuple(shape), "mean": mean,
                            "std": std, "dtype": dtype,
                            "input_dim_idx": input_dim_idx,
                            "output_dim_idx": output_dim_idx})
    return out


def uniform_random_batch_size_like(input, shape, dtype="float32",
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random_batch_size_like")
    out = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    helper.append_op(type="uniform_random_batch_size_like",
                     inputs={"Input": [input]},
                     outputs={"Out": [out]},
                     attrs={"shape": tuple(shape), "min": min,
                            "max": max, "dtype": dtype,
                            "input_dim_idx": input_dim_idx,
                            "output_dim_idx": output_dim_idx})
    return out


def add_position_encoding(input, alpha=1.0, beta=1.0, name=None):
    return _simple("add_position_encoding", input,
                   {"alpha": alpha, "beta": beta}, name=name)


def affine_grid(theta, out_shape, name=None):
    helper = LayerHelper("affine_grid", name=name)
    out = helper.create_variable_for_type_inference(theta.dtype)
    attrs = {}
    if isinstance(out_shape, (list, tuple)):
        attrs["output_shape_attr"] = tuple(out_shape)
        inputs = {"Theta": [theta]}
    else:
        inputs = {"Theta": [theta], "OutputShape": [out_shape]}
    helper.append_op(type="affine_grid", inputs=inputs,
                     outputs={"Output": [out]}, attrs=attrs)
    return out


def has_inf(x):
    return _simple("has_inf", x, out_dtype="bool", stop_gradient=True)


def has_nan(x):
    return _simple("has_nan", x, out_dtype="bool", stop_gradient=True)


def isfinite(x):
    return _simple("isfinite", x, out_dtype="bool",
                   stop_gradient=True)


def hash(input, hash_size, num_hash=1, name=None):
    return _simple("hash", input,
                   {"num_hash": num_hash, "mod_by": hash_size},
                   out_dtype="int64", stop_gradient=True, name=name)


def rank(input):
    """Rank (ndim) of a variable as a constant tensor (reference:
    layers/nn.py rank — build-time constant here, shapes are static)."""
    from . import tensor as _t
    import numpy as _np
    return _t.assign(_np.array([len(input.shape)], _np.int32))


def merge_selected_rows(x, name=None):
    return _simple("merge_selected_rows", x, name=name)


def get_tensor_from_selected_rows(x, name=None):
    return _simple("get_tensor_from_selected_rows", x, name=name)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    from . import math_op_patch as mop
    return mop.binary(x, y, "elementwise_mod")


def elementwise_floordiv(x, y, axis=-1, act=None, name=None):
    from . import math_op_patch as mop
    return mop.binary(x, y, "elementwise_floordiv")
