"""Registry-wide OpTest sweep.

Reference: the 249 test_*op*.py files under
python/paddle/fluid/tests/unittests/, all built on OpTest's dual
numeric/analytic check (op_test.py:45 get_numeric_gradient, :495
check_output, :532 check_grad).

Table-driven here: every registered op must appear either in SPECS
(swept: finite-difference grad check for differentiable ops, numpy
reference output check otherwise) or in EXEMPT with the test file that
covers it — test_coverage_ratchet enforces this, so a newly registered
op without a spec fails CI.
"""

import numpy as np
import pytest

from op_test import check_grad, check_output

from paddle_tpu import ops as op_registry


def _rs(seed):
    return np.random.RandomState(seed)


def f32(a):
    return np.asarray(a, np.float32)


def u(shape, seed=0, lo=0.25, hi=1.0):
    """Uniform floats bounded away from 0 (and from each other's
    kinks) — keeps finite differences honest for relu/abs/sqrt/log."""
    return (_rs(seed).uniform(lo, hi, shape)).astype(np.float32)


def sgn(shape, seed=0):
    """Uniform in [-1, 1] with |x| >= 0.15 (no kink straddling)."""
    x = _rs(seed).uniform(0.15, 0.9, shape)
    s = _rs(seed + 1).randint(0, 2, shape) * 2 - 1
    return (x * s).astype(np.float32)


# Each spec: (inputs, attrs, options). options keys:
#   ref:        lambda(inputs) -> list of expected outputs (positional,
#               None to skip a slot) — runs check_output
#   grad:       input slots to grad-check (differentiable ops only);
#               default: all float slots
#   out_idx:    which output the grad loss sums (default 0)
#   n_outputs:  for variadic-output ops
#   max_rel:    grad tolerance override
#   atol:       output tolerance override
SPECS = {}


def spec(name, inputs, attrs=None, **opt):
    SPECS.setdefault(name, []).append((inputs, attrs or {}, opt))


# --- unary activations / math (smooth everywhere or kink-avoided) ----
for name_, fn_, inp_ in [
    ("abs", np.abs, sgn((2, 3))),
    ("acos", np.arccos, sgn((2, 3)) * 0.8),
    ("asin", np.arcsin, sgn((2, 3)) * 0.8),
    ("atan", np.arctan, sgn((2, 3))),
    ("ceil", np.ceil, u((2, 3), lo=0.3, hi=0.7)),
    ("cos", np.cos, sgn((2, 3))),
    ("cosh", np.cosh, sgn((2, 3))),
    ("erf", None, sgn((2, 3))),
    ("exp", np.exp, sgn((2, 3))),
    ("floor", np.floor, u((2, 3), lo=0.3, hi=0.7)),
    ("log", np.log, u((2, 3), lo=0.5)),
    ("log1p", np.log1p, u((2, 3))),
    ("logsigmoid", None, sgn((2, 3))),
    ("reciprocal", lambda x: 1.0 / x, u((2, 3), lo=0.5)),
    ("relu", lambda x: np.maximum(x, 0), sgn((2, 3))),
    ("relu6", lambda x: np.clip(x, 0, 6), sgn((2, 3))),
    ("round", np.round, u((2, 3), lo=0.1, hi=0.4)),
    ("rsqrt", lambda x: x ** -0.5, u((2, 3), lo=0.5)),
    ("sigmoid", lambda x: 1 / (1 + np.exp(-x)), sgn((2, 3))),
    ("sign", np.sign, sgn((2, 3))),
    ("sin", np.sin, sgn((2, 3))),
    ("sinh", np.sinh, sgn((2, 3))),
    ("softplus", lambda x: np.log1p(np.exp(x)), sgn((2, 3))),
    ("softsign", lambda x: x / (1 + np.abs(x)), sgn((2, 3))),
    ("sqrt", np.sqrt, u((2, 3), lo=0.5)),
    ("square", np.square, sgn((2, 3))),
    ("tan", np.tan, sgn((2, 3)) * 0.7),
    ("tanh", np.tanh, sgn((2, 3))),
]:
    spec(name_, {"X": inp_},
         ref=None if fn_ is None else
         (lambda fn=fn_: (lambda ins: [fn(ins["X"])]))())

spec("assign", {"X": sgn((2, 3))}, ref=lambda ins: [ins["X"]])
spec("cast", {"X": sgn((2, 3))}, {"dtype": "float32"},
     ref=lambda ins: [ins["X"]])
spec("clip", {"X": sgn((3, 3), seed=4)}, {"min": -0.5, "max": 0.5})
spec("clip_by_norm", {"X": u((2, 3))}, {"max_norm": 0.5})
spec("elu", {"X": sgn((2, 3))}, {"alpha": 0.7})
spec("gelu", {"X": sgn((2, 3))})
spec("hard_sigmoid", {"X": sgn((2, 3)) * 0.4}, {})
spec("hard_swish", {"X": sgn((2, 3))})
spec("leaky_relu", {"X": sgn((2, 3))}, {"alpha": 0.1})
spec("increment", {"X": f32(2.5)}, {"step": 2.0},
     ref=lambda ins: [f32(4.5)])
spec("pow", {"X": u((2, 3))}, {"factor": 2.5})
spec("scale", {"X": sgn((2, 3))}, {"scale": 3.0, "bias": 0.5},
     ref=lambda ins: [ins["X"] * 3.0 + 0.5])
spec("selu", {"X": sgn((2, 3))})
spec("swish", {"X": sgn((2, 3))}, {"beta": 1.5})
spec("label_smooth", {"X": u((2, 4))}, {"epsilon": 0.1},
     ref=lambda ins: [ins["X"] * 0.9 + 0.1 / 4])
spec("prelu", {"X": sgn((2, 3)), "Alpha": f32([0.2])}, {"mode": "all"})
spec("diag", {"Diagonal": u((3,))},
     ref=lambda ins: [np.diag(ins["Diagonal"])])

# --- elementwise binary -----------------------------------------------
for name_, fn_ in [("elementwise_add", np.add),
                   ("elementwise_sub", np.subtract),
                   ("elementwise_mul", np.multiply),
                   ("elementwise_div", np.divide)]:
    spec(name_, {"X": u((2, 3), 1), "Y": u((2, 3), 2, lo=0.5)},
         ref=(lambda fn=fn_: (lambda ins: [fn(ins["X"],
                                              ins["Y"])]))())
# broadcast-with-axis variant
spec("elementwise_add", {"X": u((2, 3, 4), 3), "Y": u((3,), 4)},
     {"axis": 1},
     ref=lambda ins: [ins["X"] + ins["Y"][None, :, None]])
spec("elementwise_max",
     {"X": u((2, 3), 5), "Y": u((2, 3), 6) + 0.02})
spec("elementwise_min",
     {"X": u((2, 3), 7), "Y": u((2, 3), 8) + 0.02})
spec("elementwise_pow", {"X": u((2, 3), 9, lo=0.5),
                         "Y": u((2, 3), 10)})
spec("dot", {"X": u((4,), 11), "Y": u((4,), 12)},
     ref=lambda ins: [np.dot(ins["X"], ins["Y"])])
spec("huber_loss", {"X": u((3, 1), 13), "Y": u((3, 1), 14) + 2.0},
     {"delta": 1.0})  # |x-y| > delta everywhere: smooth branch
spec("smooth_l1_loss", {"X": u((2, 4), 15), "Y": u((2, 4), 16) + 2.0})
spec("mse_loss", {"X": u((2, 3), 17), "Y": u((2, 3), 18)},
     ref=lambda ins: [np.mean((ins["X"] - ins["Y"]) ** 2)])
spec("square_error_cost", {"X": u((2, 3), 19), "Y": u((2, 3), 20)},
     ref=lambda ins: [(ins["X"] - ins["Y"]) ** 2])
spec("kldiv_loss", {"X": u((2, 3), 21), "Target": u((2, 3), 22)},
     {"reduction": "mean"})
spec("hinge_loss", {"Logits": sgn((3, 1), 23) * 2,
                    "Labels": f32([[1], [0], [1]])})
spec("margin_rank_loss", {"X1": u((3, 1), 24) + 1.0,
                          "X2": u((3, 1), 25) - 1.0,
                          "Label": f32([[1], [1], [1]])},
     {"margin": 0.1})
spec("log_loss", {"Predicted": u((3, 1), 26, lo=0.3, hi=0.7),
                  "Labels": f32([[1], [0], [1]])})

# --- matmul family ----------------------------------------------------
spec("matmul", {"X": sgn((2, 3), 27), "Y": sgn((3, 4), 28)},
     ref=lambda ins: [ins["X"] @ ins["Y"]])
spec("matmul", {"X": sgn((3, 2), 29), "Y": sgn((4, 3), 30)},
     {"transpose_x": True, "transpose_y": True},
     ref=lambda ins: [ins["X"].T @ ins["Y"].T])
spec("mul", {"X": sgn((2, 3), 31), "Y": sgn((3, 2), 32)},
     ref=lambda ins: [ins["X"] @ ins["Y"]])
spec("fc", {"Input": sgn((2, 6), 131), "W": sgn((6, 4), 132),
            "Bias": sgn((4,), 133)},
     {"in_num_col_dims": 1, "activation_type": "relu"},
     ref=lambda ins: [np.maximum(
         ins["Input"] @ ins["W"] + ins["Bias"], 0)])
spec("fc", {"Input": sgn((2, 6), 134), "W": sgn((6, 4), 135),
            "Bias": sgn((4,), 136)},
     {"in_num_col_dims": 1, "activation_type": ""},
     ref=lambda ins: [ins["Input"] @ ins["W"] + ins["Bias"]])
def _ref_fused_xent(ins, eps):
    logits = (ins["X"] @ ins["W"]).astype(np.float64)
    m = logits.max(-1, keepdims=True)
    lse = m + np.log(np.exp(logits - m).sum(-1, keepdims=True))
    picked = np.take_along_axis(logits, ins["Label"], -1)
    V = ins["W"].shape[-1]
    return [(lse - (1 - eps) * picked
             - (eps / V) * logits.sum(-1, keepdims=True))
            .astype(np.float32)]


spec("fused_linear_xent",
     {"X": sgn((4, 6), 601), "W": sgn((6, 9), 602),
      "Label": np.array([[0], [3], [8], [5]], np.int64)},
     {"epsilon": 0.0},
     ref=lambda ins: _ref_fused_xent(ins, 0.0), max_rel=0.02)
spec("fused_linear_xent",
     {"X": sgn((4, 6), 603), "W": sgn((6, 9), 604),
      "Label": np.array([[2], [1], [7], [4]], np.int64)},
     {"epsilon": 0.1},
     ref=lambda ins: _ref_fused_xent(ins, 0.1), max_rel=0.03)
spec("fused_elemwise_activation",
     {"X": u((2, 3), 137), "Y": u((2, 3), 138)},
     {"functor_list": ["elementwise_add", "relu"], "axis": -1},
     ref=lambda ins: [np.maximum(ins["X"] + ins["Y"], 0)])
spec("fused_elemwise_activation",
     {"X": u((2, 3), 139), "Y": u((3,), 140)},
     {"functor_list": ["elementwise_add", "tanh"], "axis": 1},
     ref=lambda ins: [np.tanh(ins["X"] + ins["Y"])])

# --- reductions -------------------------------------------------------
spec("reduce_sum", {"X": sgn((2, 3), 33)},
     ref=lambda ins: [np.sum(ins["X"])])
spec("reduce_sum", {"X": sgn((2, 3, 4), 34)},
     {"dim": (1,), "keep_dim": True},
     ref=lambda ins: [np.sum(ins["X"], 1, keepdims=True)])
spec("reduce_mean", {"X": sgn((2, 3), 35)},
     ref=lambda ins: [np.mean(ins["X"])])
spec("reduce_max", {"X": u((6,), 36) + np.arange(6, dtype=np.float32)},
     ref=lambda ins: [np.max(ins["X"])])
spec("reduce_min", {"X": u((6,), 37) + np.arange(6, dtype=np.float32)},
     ref=lambda ins: [np.min(ins["X"])])
spec("reduce_prod", {"X": u((2, 3), 38, lo=0.5)},
     ref=lambda ins: [np.prod(ins["X"])])
spec("mean", {"X": sgn((2, 3), 39)},
     ref=lambda ins: [np.mean(ins["X"])])
spec("sum", {"X": [sgn((2, 3), 40), sgn((2, 3), 41),
                   sgn((2, 3), 42)]},
     ref=lambda ins: [ins["X"][0] + ins["X"][1] + ins["X"][2]])
spec("logsumexp", {"X": sgn((2, 3), 43)},
     ref=lambda ins: [np.log(np.sum(np.exp(ins["X"])))])
spec("frobenius_norm", {"X": sgn((2, 3), 44)},
     ref=lambda ins: [np.sqrt(np.sum(ins["X"] ** 2))])
spec("norm", {"X": u((2, 3), 45)}, {"axis": 1})
spec("p_norm", {"X": u((2, 3), 46)}, {"porder": 3.0, "axis": 1})
spec("l2_normalize", {"X": u((2, 3), 47)}, {"axis": 1})
spec("cumsum", {"X": sgn((2, 4), 48)}, {"axis": 1},
     ref=lambda ins: [np.cumsum(ins["X"], 1)])

# --- shape manipulation ----------------------------------------------
spec("reshape2", {"X": sgn((2, 6), 49)}, {"shape": (3, 4)},
     ref=lambda ins: [ins["X"].reshape(3, 4)])
spec("transpose2", {"X": sgn((2, 3, 4), 50)}, {"axis": (2, 0, 1)},
     ref=lambda ins: [ins["X"].transpose(2, 0, 1)])
spec("flatten2", {"X": sgn((2, 3, 4), 51)}, {"axis": 1},
     ref=lambda ins: [ins["X"].reshape(2, 12)])
spec("squeeze2", {"X": sgn((2, 1, 3), 52)}, {"axes": (1,)},
     ref=lambda ins: [ins["X"][:, 0]])
spec("unsqueeze2", {"X": sgn((2, 3), 53)}, {"axes": (1,)},
     ref=lambda ins: [ins["X"][:, None]])
spec("concat", {"X": [sgn((2, 2), 54), sgn((2, 3), 55)]},
     {"axis": 1},
     ref=lambda ins: [np.concatenate(ins["X"], 1)])
spec("stack", {"X": [sgn((2, 3), 56), sgn((2, 3), 57)]},
     {"axis": 0}, ref=lambda ins: [np.stack(ins["X"])])
spec("unstack", {"X": sgn((2, 3), 58)}, {"axis": 0}, n_outputs=2,
     ref=lambda ins: [ins["X"][0], ins["X"][1]])
spec("split", {"X": sgn((2, 6), 59)},
     {"num_or_sections": 2, "axis": 1}, n_outputs=2,
     ref=lambda ins: [ins["X"][:, :3], ins["X"][:, 3:]])
spec("slice", {"X": sgn((3, 4), 60)},
     {"axes": (0, 1), "starts": (1, 0), "ends": (3, 2)},
     ref=lambda ins: [ins["X"][1:3, 0:2]])
spec("strided_slice", {"X": sgn((4, 6), 61)},
     {"axes": (1,), "starts": (0,), "ends": (6,), "strides": (2,)},
     ref=lambda ins: [ins["X"][:, 0:6:2]])
spec("expand", {"X": sgn((1, 3), 62)}, {"expand_times": (2, 1)},
     ref=lambda ins: [np.tile(ins["X"], (2, 1))])
spec("expand_as", {"X": sgn((1, 3), 63), "Y": sgn((4, 3), 64)},
     ref=lambda ins: [np.tile(ins["X"], (4, 1))])
spec("tile", {"X": sgn((2, 2), 65)}, {"repeat_times": (1, 2)},
     ref=lambda ins: [np.tile(ins["X"], (1, 2))])
spec("pad", {"X": sgn((2, 2), 66)},
     {"paddings": (0, 1, 1, 0), "pad_value": 0.5},
     ref=lambda ins: [np.pad(ins["X"], ((0, 1), (1, 0)),
                             constant_values=0.5)])
spec("pad2d", {"X": sgn((1, 1, 2, 2), 67)},
     {"paddings": (1, 0, 0, 1)},
     ref=lambda ins: [np.pad(ins["X"],
                             ((0, 0), (0, 0), (1, 0), (0, 1)))])
spec("flip", {"X": sgn((2, 3), 68)}, {"axis": (1,)},
     ref=lambda ins: [ins["X"][:, ::-1]])
spec("roll", {"X": sgn((2, 3), 69)}, {"shifts": (1,), "axis": (1,)},
     ref=lambda ins: [np.roll(ins["X"], 1, 1)])
spec("tril_triu", {"X": sgn((3, 3), 70)},
     {"diagonal": 0, "lower": True},
     ref=lambda ins: [np.tril(ins["X"])])
spec("pixel_shuffle", {"X": sgn((1, 4, 2, 2), 71)},
     {"upscale_factor": 2})
spec("where", {"Condition": np.array([[True, False, True]]),
               "X": sgn((1, 3), 72), "Y": sgn((1, 3), 73)},
     ref=lambda ins: [np.where(ins["Condition"], ins["X"],
                               ins["Y"])])
spec("gather", {"X": sgn((4, 3), 74),
                "Index": np.array([2, 0], np.int64)},
     ref=lambda ins: [ins["X"][[2, 0]]])
spec("gather_nd", {"X": sgn((3, 3), 75),
                   "Index": np.array([[0, 1], [2, 2]], np.int64)},
     ref=lambda ins: [ins["X"][[0, 2], [1, 2]]])
spec("scatter", {"X": sgn((4, 2), 76),
                 "Ids": np.array([1, 3], np.int64),
                 "Updates": sgn((2, 2), 77)},
     {"overwrite": True})
spec("scatter_nd_add", {"X": sgn((4, 2), 78),
                        "Index": np.array([[1], [3]], np.int64),
                        "Updates": sgn((2, 2), 79)})

# --- softmax / losses -------------------------------------------------
spec("softmax", {"X": sgn((2, 4), 80)},
     loss_weight=_rs(200).uniform(0.5, 1.5, (2, 4)),
     ref=lambda ins: [np.exp(ins["X"]) /
                      np.exp(ins["X"]).sum(-1, keepdims=True)])
spec("log_softmax", {"X": sgn((2, 4), 81)})
spec("cross_entropy",
     {"X": u((2, 3), 82, lo=0.2, hi=0.8) /
      u((2, 3), 82, lo=0.2, hi=0.8).sum(-1, keepdims=True),
      "Label": np.array([[0], [2]], np.int64)})
spec("softmax_with_cross_entropy",
     {"Logits": sgn((2, 4), 83),
      "Label": np.array([[1], [3]], np.int64)},
     out_idx=1)
spec("sigmoid_cross_entropy_with_logits",
     {"X": sgn((2, 3), 84), "Label": u((2, 3), 85, lo=0.0)})

# --- NN: conv / pool / norm -------------------------------------------
spec("conv2d", {"Input": sgn((1, 2, 4, 4), 86),
                "Filter": sgn((3, 2, 2, 2), 87)},
     {"strides": (1, 1), "paddings": (0, 0)}, max_rel=0.01)
spec("conv2d_transpose", {"Input": sgn((1, 2, 3, 3), 88),
                          "Filter": sgn((2, 3, 2, 2), 89)},
     max_rel=0.01)
spec("depthwise_conv2d_transpose",
     {"Input": sgn((1, 2, 3, 3), 881), "Filter": sgn((2, 1, 2, 2), 891)},
     max_rel=0.01,
     ref=lambda ins: [__import__("torch").nn.functional.conv_transpose2d(
         __import__("torch").from_numpy(ins["Input"]),
         __import__("torch").from_numpy(ins["Filter"]),
         groups=2).numpy()])
spec("conv3d", {"Input": sgn((1, 1, 3, 3, 3), 90),
                "Filter": sgn((2, 1, 2, 2, 2), 91)}, max_rel=0.01)
spec("depthwise_conv2d", {"Input": sgn((1, 2, 4, 4), 92),
                          "Filter": sgn((2, 1, 2, 2), 93)},
     {"groups": 2}, max_rel=0.01)
spec("pool2d", {"X": sgn((1, 1, 4, 4), 94)},
     {"ksize": (2, 2), "pooling_type": "avg", "strides": (2, 2)})
spec("pool2d",
     {"X": (np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
            + u((1, 1, 4, 4), 95, lo=0.0, hi=0.3))},
     {"ksize": (2, 2), "pooling_type": "max", "strides": (2, 2)})
spec("adaptive_pool2d", {"X": sgn((1, 1, 4, 4), 96)},
     {"pool_size": (2, 2), "pooling_type": "avg"})
# ceil_mode: 5->3 tail windows, exclusive counts (pool_op.cc ceil)
spec("pool2d", {"X": sgn((1, 2, 5, 5), 964)},
     {"ksize": (2, 2), "pooling_type": "avg", "strides": (2, 2),
      "ceil_mode": True},
     ref=lambda ins: [__import__("torch").nn.functional.avg_pool2d(
         __import__("torch").from_numpy(ins["X"]), 2, 2,
         ceil_mode=True, count_include_pad=False).numpy()])
# NHWC layout: same values as the NCHW spec, channels-last
spec("pool2d", {"X": sgn((1, 4, 4, 2), 963)},
     {"ksize": (2, 2), "pooling_type": "avg", "strides": (2, 2),
      "data_format": "NHWC"},
     ref=lambda ins: [np.transpose(
         ins["X"], (0, 3, 1, 2)).reshape(1, 2, 2, 2, 2, 2)
         .mean(axis=(3, 5)).transpose(0, 2, 3, 1)])
# uneven bins: 5 -> 3 uses floor/ceil boundaries (pool_op.h:42-52)
spec("adaptive_pool2d", {"X": sgn((1, 2, 5, 7), 961)},
     {"pool_size": (3, 4), "pooling_type": "avg"})
spec("adaptive_pool2d",
     {"X": (np.arange(70, dtype=np.float32).reshape(1, 2, 5, 7)
            + u((1, 2, 5, 7), 962, lo=0.0, hi=0.3))},
     {"pool_size": (3, 4), "pooling_type": "max"})
spec("maxout",
     {"X": (np.arange(16, dtype=np.float32).reshape(1, 4, 2, 2)
            + u((1, 4, 2, 2), 97, lo=0.0, hi=0.3))},
     {"groups": 2})
spec("batch_norm", {"X": sgn((3, 2, 2, 2), 98),
                    "Scale": u((2,), 99), "Bias": sgn((2,), 100),
                    "Mean": np.zeros(2, np.float32),
                    "Variance": np.ones(2, np.float32)},
     {"is_test": False}, grad=["X", "Scale", "Bias"], max_rel=0.04,
     loss_weight=_rs(201).uniform(0.5, 1.5, (3, 2, 2, 2)))
# normalization grads vs FD: the mean-centered terms nearly cancel, so
# fp32 FD noise dominates the small components (tolerance reflects it)
spec("layer_norm", {"X": sgn((3, 4), 101), "Scale": u((4,), 102),
                    "Bias": sgn((4,), 103)},
     grad=["X", "Scale", "Bias"], max_rel=0.02)
spec("rms_norm", {"X": sgn((3, 4), 131), "Scale": u((4,), 132)},
     {"epsilon": 1e-5}, max_rel=0.02,
     ref=lambda ins: [ins["X"] / np.sqrt(
         np.mean(np.square(ins["X"]), -1, keepdims=True) + 1e-5)
         * ins["Scale"]])


def _rotary_ref(start=0, width=None, interleaved=False):
    """Lanes [start, start + width) of the head (all by default): pair
    i turns by s * theta^(-2i/width) at row s; its lanes are (i, i +
    width/2) of the part, or (2i, 2i + 1) interleaved."""
    def ref(ins):
        x = ins["X"]
        w = x.shape[-1] - start if width is None else width
        ang = np.arange(x.shape[-2])[:, None] \
            * 100.0 ** (-np.arange(w // 2) * 2.0 / w)[None, :]
        part = x[..., start:start + w]
        first, second = (slice(0, None, 2), slice(1, None, 2)) \
            if interleaved else (slice(0, w // 2), slice(w // 2, None))
        x1, x2 = part[..., first], part[..., second]
        out = x.copy()
        out[..., start:start + w][..., first] = \
            x1 * np.cos(ang) - x2 * np.sin(ang)
        out[..., start:start + w][..., second] = \
            x2 * np.cos(ang) + x1 * np.sin(ang)
        return [out.astype(np.float32)]
    return ref


spec("rotary_embedding", {"X": sgn((1, 2, 5, 4), 133)},
     {"theta": 100.0}, ref=_rotary_ref(),
     loss_weight=_rs(203).uniform(0.5, 1.5, (1, 2, 5, 4)))
# a rotary part beside plain lanes, and the interleaved pair layout
for start_, width_, inter_ in [(0, None, True), (2, 4, True),
                               (4, None, False)]:
    spec("rotary_embedding", {"X": sgn((1, 2, 5, 8), 135)},
         {"theta": 100.0, "start": start_, "width": width_ or 0,
          "interleaved": inter_},
         ref=_rotary_ref(start_, width_, inter_),
         loss_weight=_rs(205).uniform(0.5, 1.5, (1, 2, 5, 8)))
spec("gated_rms_norm", {"X": sgn((2, 3, 8), 134),
                        "Gate": sgn((2, 3, 8), 136),
                        "Scale": u((4,), 138)},
     {"epsilon": 1e-5}, max_rel=0.02,
     ref=lambda ins: [(lambda x: x / np.sqrt(
         np.mean(np.square(x), -1, keepdims=True) + 1e-5)
         * ins["Scale"])(ins["X"].reshape(2, 3, 2, 4)).reshape(2, 3, 8)
         / (1.0 + np.exp(-ins["Gate"]))])
spec("short_conv", {"X": sgn((2, 6, 3), 140), "W": sgn((3, 4), 142)},
     max_rel=0.02,
     ref=lambda ins: [(lambda y: y / (1.0 + np.exp(-y)))(sum(
         np.pad(ins["X"], ((0, 0), (3, 0), (0, 0)))[:, i:i + 6]
         * ins["W"][:, i] for i in range(4)))])
spec("kda_gate", {"X": sgn((2, 5, 6), 144), "ALog": u((2,), 146),
                  "DtBias": sgn((6,), 148)},
     grad=["X", "ALog", "DtBias"], max_rel=0.02,
     ref=lambda ins: [-np.repeat(np.exp(ins["ALog"]), 3)
                      * np.log1p(np.exp(ins["X"] + ins["DtBias"]))])
spec("instance_norm", {"X": sgn((2, 2, 3, 3), 104),
                       "Scale": u((2,), 105),
                       "Bias": sgn((2,), 106)}, max_rel=0.02,
     loss_weight=_rs(202).uniform(0.5, 1.5, (2, 2, 3, 3)))
spec("group_norm", {"X": sgn((2, 4, 2, 2), 107),
                    "Scale": u((4,), 108), "Bias": sgn((4,), 109)},
     {"groups": 2}, max_rel=0.02)
spec("grid_sampler", {"X": sgn((1, 1, 3, 3), 110),
                      "Grid": sgn((1, 2, 2, 2), 111) * 0.5},
     max_rel=0.02)
spec("interpolate", {"X": sgn((1, 1, 2, 2), 112)},
     {"out_shape": (4, 4), "method": "nearest"})
spec("interpolate", {"X": sgn((1, 1, 2, 2), 113)},
     {"out_shape": (4, 4), "method": "bilinear",
      "align_corners": True}, max_rel=0.02)
spec("lookup_table", {"W": sgn((5, 3), 114),
                      "Ids": np.array([[1], [4]], np.int64)},
     ref=lambda ins: [ins["W"][[1, 4]]])
spec("embedding_bag", {"W": sgn((5, 3), 115),
                       "Ids": np.array([[1, 2], [0, 4]], np.int64)},
     {"mode": "sum"},
     ref=lambda ins: [np.stack([ins["W"][[1, 2]].sum(0),
                                ins["W"][[0, 4]].sum(0)])])
spec("dropout", {"X": u((2, 3), 116)}, {"is_test": True},
     ref=lambda ins: [ins["X"] * 0.5], grad=[])  # train mode is rng-driven
spec("scaled_dot_product_attention",
     {"Q": sgn((1, 2, 3, 4), 117) * 0.5,
      "K": sgn((1, 2, 3, 4), 118) * 0.5,
      "V": sgn((1, 2, 3, 4), 119) * 0.5},
     {"scale": 0.5, "is_test": True}, max_rel=0.02)
spec("roi_align", {"X": sgn((1, 1, 4, 4), 120),
                   "ROIs": f32([[0, 0, 3, 3]]),
                   "RoisBatchIdx": np.array([0], np.int32)},
     {"pooled_height": 2, "pooled_width": 2, "sampling_ratio": 2},
     max_rel=0.02)
spec("roi_pool",
     {"X": (np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
            + u((1, 1, 4, 4), 121, lo=0.0, hi=0.3)),
      "ROIs": f32([[0, 0, 3, 3]]),
      "RoisBatchIdx": np.array([0], np.int32)},
     {"pooled_height": 2, "pooled_width": 2})
spec("box_clip", {"Input": f32([[[-2, -2, 5, 9]]]),
                  "ImInfo": f32([[8, 8, 1.0]])},
     ref=lambda ins: [f32([[[0, 0, 5, 7]]])], grad=[])
spec("box_coder", {"PriorBox": f32([[0, 0, 4, 4], [2, 2, 8, 8]]),
                   "TargetBox": f32([[1, 1, 3, 3]])},
     {"code_type": "encode_center_size",
      "variance": (0.1, 0.1, 0.2, 0.2)}, grad=["TargetBox"])
spec("target_assign",
     {"X": sgn((1, 3, 2), 122),
      "MatchIndices": np.array([[1, -1, 0]], np.int32)},
     grad=["X"])

# --- sequence (padded + lengths redesign) -----------------------------
_seq_x = sgn((2, 4, 3), 123)
_seq_len = np.array([3, 2], np.int64)
spec("sequence_softmax", {"X": sgn((2, 4), 124), "SeqLen": _seq_len})
spec("sequence_pool", {"X": _seq_x, "SeqLen": _seq_len},
     {"pool_type": "average"})
spec("sequence_first_step", {"X": _seq_x, "SeqLen": _seq_len},
     ref=lambda ins: [ins["X"][:, 0]])
spec("sequence_last_step", {"X": _seq_x, "SeqLen": _seq_len},
     ref=lambda ins: [np.stack([ins["X"][0, 2], ins["X"][1, 1]])])
spec("sequence_reverse", {"X": _seq_x, "SeqLen": _seq_len})
spec("sequence_concat",
     {"X": [sgn((2, 2, 3), 125), sgn((2, 3, 3), 126)],
      "SeqLen": [np.array([2, 1], np.int64),
                 np.array([2, 3], np.int64)]},
     out_idx=0)
spec("sequence_pad", {"X": _seq_x, "SeqLen": _seq_len},
     {"pad_value": 0.0, "padded_length": 5}, out_idx=0)
spec("sequence_unpad", {"X": _seq_x, "Length": _seq_len})
spec("sequence_slice", {"X": _seq_x,
                        "Offset": np.array([[1], [0]], np.int64),
                        "Length": np.array([[2], [2]], np.int64)})
spec("gru_unit", {"X": sgn((2, 9), 127), "HPrev": sgn((2, 3), 128),
                  "Weight": sgn((3, 9), 129) * 0.5,
                  "Bias": sgn((9,), 130) * 0.1}, max_rel=0.02)
spec("lstm_unit", {"X": sgn((2, 8), 131), "HPrev": sgn((2, 2), 132),
                   "CPrev": sgn((2, 2), 133),
                   "Weight": sgn((2, 8), 134) * 0.5,
                   "Bias": sgn((8,), 135) * 0.1}, max_rel=0.02)

# --- comparison / logical / fills (output checks) ---------------------
_cx, _cy = u((2, 3), 136), u((2, 3), 137)
for name_, fn_ in [("equal", np.equal), ("not_equal", np.not_equal),
                   ("less_than", np.less),
                   ("less_equal", np.less_equal),
                   ("greater_than", np.greater),
                   ("greater_equal", np.greater_equal)]:
    spec(name_, {"X": _cx, "Y": _cy},
         ref=(lambda fn=fn_: (lambda ins: [fn(ins["X"],
                                              ins["Y"])]))())
_bx = np.array([[True, False], [True, True]])
_by = np.array([[False, False], [True, False]])
spec("logical_and", {"X": _bx, "Y": _by},
     ref=lambda ins: [ins["X"] & ins["Y"]])
spec("logical_or", {"X": _bx, "Y": _by},
     ref=lambda ins: [ins["X"] | ins["Y"]])
spec("logical_xor", {"X": _bx, "Y": _by},
     ref=lambda ins: [ins["X"] ^ ins["Y"]])
spec("logical_not", {"X": _bx}, ref=lambda ins: [~ins["X"]])
spec("elementwise_floordiv",
     {"X": np.array([[7, 9]], np.int64),
      "Y": np.array([[2, 4]], np.int64)},
     ref=lambda ins: [np.array([[3, 2]], np.int64)])
spec("elementwise_mod", {"X": np.array([[7, 9]], np.int64),
                         "Y": np.array([[2, 4]], np.int64)},
     ref=lambda ins: [np.array([[1, 1]], np.int64)])
spec("fill_constant", {}, {"shape": (2, 2), "dtype": "float32",
                           "value": 1.5},
     ref=lambda ins: [np.full((2, 2), 1.5, np.float32)])
spec("fill_any_like", {"X": u((2, 3), 138)}, {"value": 2.0},
     ref=lambda ins: [np.full((2, 3), 2.0, np.float32)])
spec("fill_zeros_like", {"X": u((2, 3), 139)},
     ref=lambda ins: [np.zeros((2, 3), np.float32)])
spec("fill_constant_batch_size_like", {"Input": u((3, 2), 140)},
     {"shape": (1, 4), "dtype": "float32", "value": 0.5},
     ref=lambda ins: [np.full((3, 4), 0.5, np.float32)])
spec("eye", {}, {"num_rows": 3, "num_columns": 4},
     ref=lambda ins: [np.eye(3, 4, dtype=np.float32)])
spec("linspace", {}, {"start": 0.0, "stop": 1.0, "num": 5,
                      "dtype": "float32"},
     ref=lambda ins: [np.linspace(0, 1, 5, dtype=np.float32)])
spec("range", {}, {"start": 1.0, "end": 7.0, "step": 2.0,
                   "dtype": "int64"},
     ref=lambda ins: [np.arange(1, 7, 2, np.int64)])
spec("one_hot", {"X": np.array([[1], [3]], np.int64)}, {"depth": 4},
     ref=lambda ins: [np.eye(4, dtype=np.float32)[[1, 3]]])
spec("shape", {"X": u((3, 5), 141)},
     ref=lambda ins: [np.array([3, 5], np.int32)])
spec("is_empty", {"X": u((2,), 142)},
     ref=lambda ins: [np.asarray(False)])
spec("isnan", {"X": f32([1.0, np.nan])},
     ref=lambda ins: [np.array([False, True])])
spec("isinf", {"X": f32([1.0, np.inf])},
     ref=lambda ins: [np.array([False, True])])
spec("isfinite", {"X": f32([1.0, np.inf])},
     ref=lambda ins: [np.array([True, False])])
spec("arg_max", {"X": f32([[1, 5, 2], [7, 0, 3]])},
     ref=lambda ins: [np.array([1, 0], np.int32)])
spec("arg_min", {"X": f32([[1, 5, 2], [7, 0, 3]])},
     ref=lambda ins: [np.array([0, 1], np.int32)])
spec("argsort", {"X": f32([[3, 1, 2]])},
     ref=lambda ins: [f32([[1, 2, 3]]),
                      np.array([[1, 2, 0]], np.int32)])
spec("top_k", {"X": f32([[1, 5, 2, 7]])}, {"k": 2},
     ref=lambda ins: [f32([[7, 5]]),
                      np.array([[3, 1]], np.int64)])
spec("sequence_mask", {"X": np.array([2, 3], np.int64)},
     {"maxlen": 4},
     ref=lambda ins: [f32([[1, 1, 0, 0], [1, 1, 1, 0]])])
spec("sequence_enumerate",
     {"X": np.array([[1, 2, 3, 0]], np.int64),
      "SeqLen": np.array([3], np.int64)},
     {"win_size": 2, "pad_value": 0})
spec("reduce_all", {"X": _bx},
     ref=lambda ins: [np.asarray(False)])
spec("reduce_any", {"X": _by}, {"dim": (1,)},
     ref=lambda ins: [np.array([False, True])])
spec("cum_step_counter", {"X": np.asarray(4, np.int64)},
     ref=lambda ins: [np.asarray(5, np.int64)])
spec("iou_similarity", {"X": f32([[0, 0, 2, 2]]),
                        "Y": f32([[0, 0, 2, 2], [1, 1, 3, 3]])},
     ref=lambda ins: [f32([[1.0, 1.0 / 7.0]])])
spec("polygon_box_transform",
     {"Input": np.zeros((1, 2, 2, 2), np.float32)},
     ref=lambda ins: [np.stack([
         np.tile(f32([0, 4]), (2, 1)),
         np.repeat(f32([0, 4]), 2).reshape(2, 2)])[None]])
spec("sgd", {"Param": u((3,), 143), "Grad": u((3,), 144),
             "LearningRate": f32(0.5)},
     ref=lambda ins: [ins["Param"] - 0.5 * ins["Grad"]])
spec("lookup_table_grad",
     {"Ids": np.array([[1], [1]], np.int64),
      "OutGrad": f32([[[1, 2]], [[3, 4]]])},
     {"height": 4})
spec("grad_accumulate", {"Acc": f32([1.0]), "Grad": f32([2.0]),
                         "ShouldApply": np.asarray(False)},
     {"k": 2.0},
     ref=lambda ins: [f32([3.0]), f32([1.5])])
spec("accum_steps_counter", {"Counter": np.asarray(1, np.int32)},
     {"k": 2},
     ref=lambda ins: [np.asarray(0, np.int32), np.asarray(True)])
spec("ema_apply", {"Ema": f32([0.5]), "DecayPow": f32(0.5)},
     ref=lambda ins: [f32([1.0])])
spec("model_average_apply",
     {"Sum1": f32([2.0]), "Sum2": f32([4.0]), "Sum3": f32([0.0]),
      "NumAccumulates": np.asarray(2, np.int64),
      "OldNumAccumulates": np.asarray(1, np.int64)},
     ref=lambda ins: [f32([2.0])])
# random ops: shape/dtype/range contracts
spec("gaussian_random", {}, {"shape": (64,), "mean": 0.0,
                             "std": 1.0},
     ref=None, custom="random_normal")
spec("uniform_random", {}, {"shape": (64,), "min": -1.0, "max": 1.0},
     ref=None, custom="random_uniform")
spec("truncated_gaussian_random", {}, {"shape": (64,), "std": 1.0},
     ref=None, custom="random_truncated")
spec("randint", {}, {"shape": (64,), "low": 0, "high": 5},
     ref=None, custom="random_int")
spec("randperm", {}, {"n": 16}, ref=None, custom="random_perm")


def _np_qdq(x, scale, bits=8):
    qmax = 2.0 ** (bits - 1) - 1
    import numpy as _np
    s_ = max(float(scale), 1e-8)
    return _np.clip(_np.round(x / s_ * qmax), -qmax, qmax) * s_ / qmax


_qx = sgn((2, 3), 210)
def _np_q8_sync(x, r, bs):
    """Numpy twin of quant_allreduce's single-device path: compensate
    with the residual, one block-scaled int8 round trip, carry the
    quantization error forward (parallel/collectives.all_reduce_q8)."""
    c = (x + r).astype(np.float32)
    flat = c.reshape(-1)
    nblk = -(-flat.size // bs)
    pad = np.zeros(nblk * bs, np.float32)
    pad[:flat.size] = flat
    blocks = pad.reshape(nblk, bs)
    amax = np.abs(blocks).max(axis=1)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(blocks / scale[:, None]), -127, 127)
    y = (q * scale[:, None]).reshape(-1)[:flat.size].reshape(c.shape)
    return [y.astype(np.float32), c - y]


# both outputs checked within half a quantization step (atol covers
# the base q8 lowering AND the lossless "exact" variant rerun, whose
# Out=X+R / ResidualOut=0 differ from the q8 reference by <= scale/2)
spec("quant_allreduce",
     {"X": sgn((4, 8), 920), "Residual": np.zeros((4, 8), np.float32)},
     {"block_size": 8},
     ref=lambda ins: _np_q8_sync(ins["X"], ins["Residual"], 8),
     atol=0.01)
spec("quant_allreduce",
     {"X": sgn((3, 7), 921), "Residual": sgn((3, 7), 922) * 0.01},
     {"block_size": 4},
     ref=lambda ins: _np_q8_sync(ins["X"], ins["Residual"], 4),
     atol=0.01)

spec("fake_quantize_dequantize_abs_max", {"X": _qx},
     ref=lambda ins: [_np_qdq(ins["X"], np.abs(ins["X"]).max()),
                      np.abs(ins["X"]).max()],
     grad=[])  # STE grad is identity by design; numeric sees steps
spec("dequantize_weight",
     {"X": np.array([[127, -127], [64, 0]], np.int8),
      "Scale": f32(0.5)},
     ref=lambda ins: [ins["X"].astype(np.float32) * 0.5 / 127.0])


def _np_quant(x, scale, bits=8):
    qmax = 2.0 ** (bits - 1) - 1
    s_ = max(float(scale), 1e-8)
    return np.clip(np.round(x / s_ * qmax), -qmax, qmax)


spec("fake_quantize_abs_max", {"X": _qx},
     ref=lambda ins: [_np_quant(ins["X"], np.abs(ins["X"]).max()),
                      np.abs(ins["X"]).max()],
     grad=[])
spec("fake_quantize_range_abs_max",
     {"X": _qx, "InScale": f32(0.0),
      "Iter": np.int32(0), "ScalesBuffer": np.zeros(4, np.float32)},
     {"window_size": 4},
     ref=lambda ins: [
         _np_quant(ins["X"], np.abs(ins["X"]).max()),
         np.abs(ins["X"]).max(),
         np.array([np.abs(ins["X"]).max(), 0, 0, 0], np.float32),
         np.int32(1)],
     grad=[], n_outputs=4)
spec("fake_quantize_moving_average_abs_max",
     {"X": _qx, "InScale": f32(0.0), "InAccum": f32(0.0),
      "InState": f32(0.0)},
     {"moving_rate": 0.9},
     ref=lambda ins: [
         _np_quant(ins["X"], np.abs(ins["X"]).max()),
         np.abs(ins["X"]).max(),
         np.abs(ins["X"]).max(), f32(1.0)],
     grad=[], n_outputs=4)
spec("fake_channel_wise_quantize_abs_max", {"X": sgn((3, 4), 212)},
     {"quant_axis": 0},
     ref=lambda ins: [
         np.stack([_np_quant(r, np.abs(r).max()) for r in ins["X"]]),
         np.abs(ins["X"]).max(axis=1)],
     grad=[], n_outputs=2)
spec("moving_average_abs_max_scale",
     {"X": _qx, "InAccum": f32(0.0), "InState": f32(0.0)},
     {"moving_rate": 0.9},
     ref=lambda ins: [ins["X"], np.abs(ins["X"]).max(),
                      np.abs(ins["X"]).max(), f32(1.0)],
     grad=[], n_outputs=4)
spec("fake_dequantize_max_abs",
     {"X": np.array([[127.0, -64.0]], np.float32), "Scale": f32(0.5)},
     {"max_range": 127.0},
     ref=lambda ins: [ins["X"] * 0.5 / 127.0], grad=[])
spec("fake_channel_wise_dequantize_max_abs",
     {"X": np.array([[127.0, -64.0], [32.0, 0.0]], np.float32),
      "Scales": [np.array([0.5, 0.25], np.float32)]},
     {"quant_bits": (8,), "quant_axis": 0},
     ref=lambda ins: [ins["X"] *
                      np.array([[0.5], [0.25]], np.float32) / 127.0],
     grad=[])
spec("fsp_matrix",
     {"X": sgn((2, 3, 4, 4), 213), "Y": sgn((2, 5, 4, 4), 214)},
     ref=lambda ins: [np.einsum("bihw,bjhw->bij", ins["X"],
                                ins["Y"]) / 16.0])

spec("brelu", {"X": sgn((2, 4), 750)}, {"t_min": -0.5, "t_max": 0.5},
     ref=lambda ins: [np.clip(ins["X"], -0.5, 0.5)])
spec("soft_relu", {"X": sgn((2, 4), 751)}, {"threshold": 40.0},
     ref=lambda ins: [np.log1p(np.exp(ins["X"]))])
spec("stanh", {"X": sgn((2, 4), 752)},
     {"scale_a": 0.67, "scale_b": 1.7159},
     ref=lambda ins: [1.7159 * np.tanh(0.67 * ins["X"])])
spec("adaptive_pool3d", {"X": u((1, 2, 4, 4, 4), 753)},
     {"pool_size": 2, "pooling_type": "avg"},
     ref=lambda ins: [ins["X"].reshape(1, 2, 2, 2, 2, 2, 2, 2)
                      .mean(axis=(3, 5, 7))])
spec("dice_loss", {"X": u((2, 4), 754, lo=0.1, hi=0.9),
                   "Label": (u((2, 4), 755) > 0.6)
                   .astype(np.float32)},
     ref=lambda ins: [np.float32(np.mean(
         1 - (2 * (ins["X"] * ins["Label"]).sum(1) + 1e-5)
         / (ins["X"].sum(1) + ins["Label"].sum(1) + 1e-5)))])
spec("npair_loss", {"Anchor": sgn((3, 4), 756),
                    "Positive": sgn((3, 4), 757),
                    "Labels": np.array([[0], [1], [0]], np.int64)},
     {"l2_reg": 0.0}, max_rel=0.02)
spec("has_inf", {"X": np.array([1.0, np.inf], np.float32)},
     ref=lambda ins: [np.bool_(True)])
spec("has_nan", {"X": np.array([1.0, 2.0], np.float32)},
     ref=lambda ins: [np.bool_(False)])
spec("hash", {"X": np.array([[1, 2], [3, 4]], np.int64)},
     {"num_hash": 2, "mod_by": 1000})

# --- optimizer update ops: independent numpy references --------------
# (replacing the former test-file exemptions — the sweep now checks
# each update rule against the textbook equations directly)

def _opt_common(seed):
    return {"Param": sgn((3, 4), seed), "Grad": sgn((3, 4), seed + 1),
            "LearningRate": f32(0.1)}


def _ref_momentum(ins, mu, nesterov):
    v = mu * ins["Velocity"] + ins["Grad"]
    if nesterov:
        p = ins["Param"] - (ins["Grad"] + mu * v) * 0.1
    else:
        p = ins["Param"] - 0.1 * v
    return [p, v]


spec("momentum", dict(_opt_common(700), Velocity=sgn((3, 4), 702)),
     {"mu": 0.9}, ref=lambda ins: _ref_momentum(ins, 0.9, False),
     n_outputs=2)
spec("momentum", dict(_opt_common(703), Velocity=sgn((3, 4), 705)),
     {"mu": 0.9, "use_nesterov": True},
     ref=lambda ins: _ref_momentum(ins, 0.9, True), n_outputs=2)


def _ref_lars(ins, mu=0.9, coeff=0.001, wd=0.0005, eps=1e-9):
    p, g, v = ins["Param"], ins["Grad"], ins["Velocity"]
    pn = np.sqrt((p * p).sum())
    gn = np.sqrt((g * g).sum())
    local = 0.1 * coeff * pn / (gn + wd * pn + eps)
    vn = mu * v + local * (g + wd * p)
    return [p - vn, vn]


spec("lars_momentum", dict(_opt_common(706), Velocity=sgn((3, 4), 708)),
     {"mu": 0.9}, ref=_ref_lars, n_outputs=2)


def _ref_adam(ins, b1=0.9, b2=0.999, eps=1e-8, wd=None):
    m1 = b1 * ins["Moment1"] + (1 - b1) * ins["Grad"]
    m2 = b2 * ins["Moment2"] + (1 - b2) * ins["Grad"] ** 2
    lr_t = 0.1 * np.sqrt(1 - ins["Beta2Pow"]) / (1 - ins["Beta1Pow"])
    p = ins["Param"] - lr_t * m1 / (np.sqrt(m2) + eps)
    if wd is not None:
        p = p - 0.1 * wd * ins["Param"]
    return [p, m1, m2, ins["Beta1Pow"] * b1, ins["Beta2Pow"] * b2]


_adam_state = dict(Moment1=sgn((3, 4), 710), Moment2=u((3, 4), 711),
                   Beta1Pow=f32(0.9 ** 3), Beta2Pow=f32(0.999 ** 3))
spec("adam", dict(_opt_common(712), **_adam_state), {},
     ref=lambda ins: _ref_adam(ins), n_outputs=5)
spec("adamw", dict(_opt_common(714), **_adam_state),
     {"weight_decay": 0.01},
     ref=lambda ins: _ref_adam(ins, wd=0.01), n_outputs=5)


def _ref_adamax(ins, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * ins["Moment"] + (1 - b1) * ins["Grad"]
    inf = np.maximum(b2 * ins["InfNorm"], np.abs(ins["Grad"]))
    lr_t = 0.1 / (1 - ins["Beta1Pow"])
    return [ins["Param"] - lr_t * m / (inf + eps), m, inf,
            ins["Beta1Pow"] * b1]


spec("adamax", dict(_opt_common(716), Moment=sgn((3, 4), 718),
                    InfNorm=u((3, 4), 719), Beta1Pow=f32(0.9 ** 2)),
     {}, ref=_ref_adamax, n_outputs=4)


def _ref_adagrad(ins, eps=1e-6):
    m = ins["Moment"] + ins["Grad"] ** 2
    return [ins["Param"] - 0.1 * ins["Grad"] / (np.sqrt(m) + eps), m]


spec("adagrad", dict(_opt_common(720), Moment=u((3, 4), 722)),
     {}, ref=_ref_adagrad, n_outputs=2)


def _ref_dec_adagrad(ins, decay=0.95, eps=1e-6):
    m = decay * ins["Moment"] + (1 - decay) * ins["Grad"] ** 2
    return [ins["Param"] - 0.1 * ins["Grad"] / (np.sqrt(m) + eps), m]


spec("decayed_adagrad", dict(_opt_common(723), Moment=u((3, 4), 725)),
     {}, ref=_ref_dec_adagrad, n_outputs=2)


def _ref_adadelta(ins, rho=0.95, eps=1e-6):
    asg = rho * ins["AvgSquaredGrad"] + (1 - rho) * ins["Grad"] ** 2
    upd = -np.sqrt((ins["AvgSquaredUpdate"] + eps) / (asg + eps)) * \
        ins["Grad"]
    asu = rho * ins["AvgSquaredUpdate"] + (1 - rho) * upd ** 2
    return [ins["Param"] + upd, asg, asu]


spec("adadelta", {"Param": sgn((3, 4), 726), "Grad": sgn((3, 4), 727),
                  "AvgSquaredGrad": u((3, 4), 728),
                  "AvgSquaredUpdate": u((3, 4), 729)},
     {}, ref=_ref_adadelta, n_outputs=3)


def _ref_rmsprop(ins, rho=0.95, eps=1e-6, mom=0.6, centered=False):
    ms = rho * ins["MeanSquare"] + (1 - rho) * ins["Grad"] ** 2
    if centered:
        mg = rho * ins["MeanGrad"] + (1 - rho) * ins["Grad"]
        denom = ms - mg ** 2 + eps
    else:
        mg = ins["MeanGrad"]
        denom = ms + eps
    m = mom * ins["Moment"] + 0.1 * ins["Grad"] / np.sqrt(denom)
    return [ins["Param"] - m, m, ms, mg]


_rms_state = dict(Moment=sgn((3, 4), 731), MeanSquare=u((3, 4), 732),
                  MeanGrad=sgn((3, 4), 733))
spec("rmsprop", dict(_opt_common(734), **_rms_state),
     {"momentum": 0.6},
     ref=lambda ins: _ref_rmsprop(ins), n_outputs=4)
spec("rmsprop", dict(_opt_common(736), **_rms_state),
     {"momentum": 0.6, "centered": True},
     ref=lambda ins: _ref_rmsprop(ins, centered=True), n_outputs=4)


def _ref_ftrl(ins, l1=0.1, l2=0.1, lp=-0.5):
    sq, lin = ins["SquaredAccumulator"], ins["LinearAccumulator"]
    nsq = sq + ins["Grad"] ** 2
    sigma = (nsq ** -lp - sq ** -lp) / 0.1
    nlin = lin + ins["Grad"] - sigma * ins["Param"]
    x = l1 * np.sign(nlin) - nlin
    y = nsq ** -lp / 0.1 + 2 * l2
    p = np.where(np.abs(nlin) > l1, x / y, 0.0).astype(np.float32)
    return [p, nsq, nlin]


spec("ftrl", dict(_opt_common(738),
                  SquaredAccumulator=u((3, 4), 740),
                  LinearAccumulator=sgn((3, 4), 741)),
     {"l1": 0.1, "l2": 0.1},
     ref=_ref_ftrl, n_outputs=3)


def _ref_lamb(ins, b1=0.9, b2=0.999, eps=1e-6, wd=0.01):
    m1 = b1 * ins["Moment1"] + (1 - b1) * ins["Grad"]
    m2 = b2 * ins["Moment2"] + (1 - b2) * ins["Grad"] ** 2
    m1h = m1 / (1 - ins["Beta1Pow"])
    m2h = m2 / (1 - ins["Beta2Pow"])
    r = m1h / (np.sqrt(m2h) + eps) + wd * ins["Param"]
    wn = np.sqrt((ins["Param"] ** 2).sum())
    rn = np.sqrt((r ** 2).sum())
    ratio = wn / rn if wn > 0 and rn > 0 else 1.0
    return [ins["Param"] - 0.1 * ratio * r, m1, m2,
            ins["Beta1Pow"] * b1, ins["Beta2Pow"] * b2]


spec("lamb", dict(_opt_common(742), **_adam_state), {},
     ref=_ref_lamb, n_outputs=5)


def _ref_proximal(ins, l1=0.05, l2=0.1):
    prox = ins["Param"] - 0.1 * ins["Grad"]
    prox = np.sign(prox) * np.maximum(np.abs(prox) - 0.1 * l1, 0.0)
    return [prox / (1.0 + 0.1 * l2)]


spec("proximal_gd", _opt_common(744), {"l1": 0.05, "l2": 0.1},
     ref=_ref_proximal)


# Ops exercised end-to-end in dedicated test files (the table must
# still account for them — the ratchet below fails on unlisted ops).
# --- loss / sequence-labeling ops (loss_ops.py) ----------------------

def _ctc_brute(logp, labels, T_len, L_len, blank=0):
    """Brute-force CTC NLL: enumerate every alignment path."""
    import itertools
    B, T, C = logp.shape
    out = []
    for b in range(B):
        lab = list(labels[b][:L_len[b]])
        total = -np.inf
        for path in itertools.product(range(C), repeat=int(T_len[b])):
            # collapse: remove repeats then blanks
            col, prev = [], -1
            for s in path:
                if s != prev and s != blank:
                    col.append(s)
                prev = s
            if col == lab:
                lp = sum(logp[b, t, s] for t, s in enumerate(path))
                total = np.logaddexp(total, lp)
        out.append(-total)
    return np.asarray(out, np.float32).reshape(-1, 1)


def _ctc_ref(ins):
    logits = ins["Logits"]
    logp = logits - np.log(np.sum(np.exp(logits), -1, keepdims=True))
    return [_ctc_brute(logp, ins["Label"].astype(int),
                       ins["LogitsLength"].reshape(-1).astype(int),
                       ins["LabelLength"].reshape(-1).astype(int))]


spec("warpctc",
     {"Logits": sgn((2, 4, 3), 201), "Label": np.array(
         [[1, 2], [2, 0]], np.int64),
      "LogitsLength": np.array([4, 3], np.int64),
      "LabelLength": np.array([2, 1], np.int64)},
     ref=_ctc_ref, grad=["Logits"], max_rel=0.01)


def _crf_brute(ins):
    import itertools
    em, tr = ins["Emission"], ins["Transition"]
    lab = ins["Label"].astype(int)
    lens = ins["Length"].reshape(-1).astype(int)
    start, stop, trans = tr[0], tr[1], tr[2:]
    B, T, D = em.shape
    out = []
    for b in range(B):
        L = lens[b]

        def score(seq):
            s = start[seq[0]] + em[b, 0, seq[0]]
            for t in range(1, L):
                s += trans[seq[t - 1], seq[t]] + em[b, t, seq[t]]
            return s + stop[seq[L - 1]]
        gold = score(lab[b][:L])
        z = -np.inf
        for seq in itertools.product(range(D), repeat=int(L)):
            z = np.logaddexp(z, score(seq))
        out.append(gold - z)
    return [np.asarray(out, np.float32).reshape(-1, 1)]


def _crf_decode_brute(ins):
    import itertools
    em, tr = ins["Emission"], ins["Transition"]
    lens = ins["Length"].reshape(-1).astype(int)
    start, stop, trans = tr[0], tr[1], tr[2:]
    B, T, D = em.shape
    paths = np.zeros((B, T), np.int32)
    for b in range(B):
        L = lens[b]
        best, best_s = None, -np.inf
        for seq in itertools.product(range(D), repeat=int(L)):
            s = start[seq[0]] + em[b, 0, seq[0]]
            for t in range(1, L):
                s += trans[seq[t - 1], seq[t]] + em[b, t, seq[t]]
            s += stop[seq[L - 1]]
            if s > best_s:
                best, best_s = seq, s
        paths[b, :L] = best
    return [paths]


_crf_ins = {"Emission": sgn((2, 4, 3), 203),
            "Transition": sgn((5, 3), 204),
            "Label": np.array([[0, 2, 1, 0], [1, 0, 0, 0]], np.int64),
            "Length": np.array([4, 2], np.int64)}
spec("linear_chain_crf", dict(_crf_ins), ref=_crf_brute,
     grad=["Emission", "Transition"], max_rel=0.01)
spec("crf_decoding",
     {k: v for k, v in _crf_ins.items() if k != "Label"},
     ref=_crf_decode_brute)


def _edit_ref(ins):
    h, r = ins["Hyps"].astype(int), ins["Refs"].astype(int)
    hl = ins["HypsLength"].reshape(-1).astype(int)
    rl = ins["RefsLength"].reshape(-1).astype(int)
    out = []
    for b in range(len(h)):
        a, c = list(h[b][:hl[b]]), list(r[b][:rl[b]])
        d = np.zeros((len(a) + 1, len(c) + 1))
        d[:, 0] = np.arange(len(a) + 1)
        d[0, :] = np.arange(len(c) + 1)
        for i in range(1, len(a) + 1):
            for j in range(1, len(c) + 1):
                d[i, j] = min(d[i - 1, j] + 1, d[i, j - 1] + 1,
                              d[i - 1, j - 1] + (a[i - 1] != c[j - 1]))
        out.append(d[-1, -1])
    return [np.asarray(out, np.float32).reshape(-1, 1), None]


spec("edit_distance",
     {"Hyps": np.array([[1, 2, 3, 4], [5, 5, 0, 0]], np.int64),
      "Refs": np.array([[1, 3, 3], [5, 6, 7]], np.int64),
      "HypsLength": np.array([4, 2], np.int64),
      "RefsLength": np.array([3, 3], np.int64)},
     ref=_edit_ref, n_outputs=2)


def _ctc_align_ref(ins):
    ids = ins["Input"].astype(int)
    lens = ins["InputLength"].reshape(-1).astype(int)
    B, T = ids.shape
    out = np.zeros((B, T), np.int32)
    olen = np.zeros((B, 1), np.int32)
    for b in range(B):
        prev, row = -1, []
        for t in range(lens[b]):
            if ids[b, t] != 0 and ids[b, t] != prev:
                row.append(ids[b, t])
            prev = ids[b, t]
        out[b, :len(row)] = row
        olen[b, 0] = len(row)
    return [out, olen]


spec("ctc_align",
     {"Input": np.array([[1, 1, 0, 2, 2, 3], [0, 0, 1, 0, 1, 1]],
                        np.int64),
      "InputLength": np.array([6, 5], np.int64)},
     ref=_ctc_align_ref, n_outputs=2)

spec("rank_loss", {"Label": f32(_rs(205).randint(0, 2, (4, 1))),
                   "Left": sgn((4, 1), 206), "Right": sgn((4, 1), 207)},
     ref=lambda ins: [np.log1p(np.exp(ins["Left"] - ins["Right"])) -
                      ins["Label"] * (ins["Left"] - ins["Right"])])
spec("bpr_loss", {"X": sgn((3, 4), 208),
                  "Label": np.array([[0], [2], [3]], np.int64)})
spec("modified_huber_loss",
     {"X": sgn((3, 1), 209), "Y": f32(_rs(210).randint(0, 2, (3, 1)))},
     ref=lambda ins: [np.where(
         ins["X"] * (2 * ins["Y"] - 1) >= -1,
         np.square(np.maximum(1 - ins["X"] * (2 * ins["Y"] - 1), 0)),
         -4 * ins["X"] * (2 * ins["Y"] - 1))])
spec("teacher_student_sigmoid_loss",
     {"X": sgn((4, 1), 211), "Label": u((4, 1), 212, lo=0.2, hi=0.8)})
spec("cos_sim", {"X": sgn((3, 4), 213), "Y": sgn((3, 4), 214)},
     ref=lambda ins: [
         (ins["X"] * ins["Y"]).sum(-1, keepdims=True) /
         (np.linalg.norm(ins["X"], axis=-1, keepdims=True) *
          np.linalg.norm(ins["Y"], axis=-1, keepdims=True)),
         None, None],
     n_outputs=3)
spec("squared_l2_distance",
     {"X": sgn((3, 4), 215), "Y": sgn((3, 4), 216)},
     ref=lambda ins: [np.square(ins["X"] - ins["Y"]).sum(
         -1, keepdims=True), None], n_outputs=2)
spec("squared_l2_norm", {"X": sgn((3, 4), 217)},
     ref=lambda ins: [np.square(ins["X"]).sum().reshape(1)])
spec("l1_norm", {"X": sgn((3, 4), 218)},
     ref=lambda ins: [np.abs(ins["X"]).sum().reshape(1)])
spec("bilinear_tensor_product",
     {"X": sgn((3, 4), 219), "Y": sgn((3, 5), 220),
      "Weight": sgn((2, 4, 5), 221), "Bias": sgn((1, 2), 222)},
     ref=lambda ins: [np.einsum("bm,smn,bn->bs", ins["X"],
                                ins["Weight"], ins["Y"]) +
                      ins["Bias"]])
spec("hierarchical_sigmoid",
     {"X": sgn((3, 4), 223), "W": sgn((5, 4), 224),
      "Bias": sgn((5,), 225),
      "Label": np.array([[0], [3], [5]], np.int64)},
     {"num_classes": 6}, grad=["X", "W", "Bias"], n_outputs=2,
     max_rel=0.01)

# --- vision ops (vision_ops.py) ---------------------------------------


def well_sep(shape, seed=0, span=3.0):
    """Values with pairwise gaps > 2*FD-delta — max-pooling numeric
    grads need the winner to stay the winner under perturbation."""
    n = int(np.prod(shape))
    vals = np.linspace(-span, span, n, dtype=np.float32)
    return _rs(seed).permutation(vals).reshape(shape)


def _lrn_ref(ins, n=5, k=1.0, alpha=1e-4, beta=0.75):
    x = ins["X"]
    B, C, H, W = x.shape
    sq = np.square(x)
    mid = np.full_like(x, k)
    half = n // 2
    for c in range(C):
        lo, hi = max(0, c - half), min(C, c + n - half)
        mid[:, c] += alpha * sq[:, lo:hi].sum(1)
    return [x * np.power(mid, -beta), None]


spec("lrn", {"X": u((2, 6, 4, 4), 230)}, ref=_lrn_ref, n_outputs=2)
spec("affine_channel",
     {"X": sgn((2, 3, 4, 4), 231), "Scale": u((3,), 232),
      "Bias": sgn((3,), 233)},
     ref=lambda ins: [ins["X"] * ins["Scale"].reshape(1, 3, 1, 1) +
                      ins["Bias"].reshape(1, 3, 1, 1)])
spec("data_norm",
     {"X": sgn((4, 3), 234), "BatchSize": f32([10, 10, 10]),
      "BatchSum": f32([5, -3, 1]), "BatchSquareSum": f32([12, 8, 9])},
     ref=lambda ins: [
         (ins["X"] - ins["BatchSum"] / 10) /
         np.sqrt(ins["BatchSquareSum"] / 10 -
                 np.square(ins["BatchSum"] / 10) + 1e-4),
         None, None],
     n_outputs=3, grad=["X"])
spec("spectral_norm",
     {"Weight": sgn((4, 3), 235), "U": u((4,), 236), "V": u((3,), 237)},
     {"power_iters": 2})
spec("sync_batch_norm",
     {"X": sgn((4, 3, 2, 2), 238), "Scale": u((3,), 239),
      "Bias": sgn((3,), 240), "Mean": f32([0.1, -0.1, 0.0]),
      "Variance": f32([1.0, 0.5, 2.0])},
     {"is_test": True, "epsilon": 1e-5},
     ref=lambda ins: [
         (ins["X"] - ins["Mean"].reshape(1, 3, 1, 1)) *
         ins["Scale"].reshape(1, 3, 1, 1) /
         np.sqrt(ins["Variance"].reshape(1, 3, 1, 1) + 1e-5) +
         ins["Bias"].reshape(1, 3, 1, 1),
         None, None, None, None],
     n_outputs=5, grad=["X"])


def _pool3d_ref(ins, ks=2):
    x = ins["X"]
    B, C, D, H, W = x.shape
    out = x.reshape(B, C, D // ks, ks, H // ks, ks, W // ks, ks) \
        .max((3, 5, 7))
    return [out]


spec("pool3d", {"X": well_sep((1, 2, 4, 4, 4), 241)},
     {"ksize": (2, 2, 2), "strides": (2, 2, 2)}, ref=_pool3d_ref)


def _maxpool_idx_ref(ins, ks=2):
    x = ins["X"]
    B, C, H, W = x.shape
    out = np.zeros((B, C, H // ks, W // ks), x.dtype)
    idx = np.zeros((B, C, H // ks, W // ks), np.int32)
    for b in range(B):
        for c in range(C):
            for i in range(H // ks):
                for j in range(W // ks):
                    patch = x[b, c, i * ks:(i + 1) * ks,
                              j * ks:(j + 1) * ks]
                    out[b, c, i, j] = patch.max()
                    a = patch.argmax()
                    idx[b, c, i, j] = (i * ks + a // ks) * W + \
                        (j * ks + a % ks)
    return [out, idx]


spec("max_pool2d_with_index", {"X": well_sep((1, 2, 4, 4), 242)},
     {"ksize": (2, 2), "strides": (2, 2)}, ref=_maxpool_idx_ref,
     n_outputs=2)
spec("max_pool3d_with_index", {"X": well_sep((1, 1, 2, 2, 2), 243)},
     {"ksize": (2, 2, 2), "strides": (2, 2, 2)}, n_outputs=2)


def _unpool_ref(ins):
    x, idx = ins["X"], ins["Indices"].astype(int)
    B, C, Hp, Wp = x.shape
    out = np.zeros((B, C, 4, 4), x.dtype)
    for b in range(B):
        for c in range(C):
            for p in range(Hp * Wp):
                f = idx[b, c].reshape(-1)[p]
                out[b, c, f // 4, f % 4] += x[b, c].reshape(-1)[p]
    return [out]


_unpool_x = sgn((1, 2, 2, 2), 244)
_unpool_idx = np.array([[[[0, 3], [9, 14]], [[5, 6], [8, 15]]]],
                       np.int32)
spec("unpool", {"X": _unpool_x, "Indices": _unpool_idx},
     {"ksize": (2, 2), "strides": (2, 2)}, ref=_unpool_ref)

spec("spp", {"X": well_sep((2, 3, 8, 8), 245, span=4.0)},
     {"pyramid_height": 2})
spec("temporal_shift", {"X": sgn((4, 4, 2, 2), 246)},
     {"seg_num": 2, "shift_ratio": 0.25})
spec("shuffle_channel", {"X": sgn((2, 6, 2, 2), 247)}, {"group": 3},
     ref=lambda ins: [ins["X"].reshape(2, 3, 2, 2, 2)
                      .transpose(0, 2, 1, 3, 4).reshape(2, 6, 2, 2)])
spec("space_to_depth", {"X": sgn((1, 2, 4, 4), 248)}, {"blocksize": 2})
spec("crop", {"X": sgn((4, 5), 249)},
     {"shape": (2, 3), "offsets_attr": (1, 1)},
     ref=lambda ins: [ins["X"][1:3, 1:4]])
spec("pad_constant_like",
     {"X": sgn((4, 5), 250), "Y": sgn((2, 3), 251)},
     {"pad_value": 0.5}, grad=["Y"],
     ref=lambda ins: [np.pad(ins["Y"], ((0, 2), (0, 2)),
                             constant_values=0.5)])
spec("multiplex",
     {"Ids": np.array([[1], [0], [1]], np.int64),
      "X": [sgn((3, 4), 252), sgn((3, 4), 253)]},
     ref=lambda ins: [np.stack([ins["X"][i][b] for b, i in
                                enumerate([1, 0, 1])])])
spec("reverse", {"X": sgn((3, 4), 254)}, {"axis": [1]},
     ref=lambda ins: [ins["X"][:, ::-1]])
spec("nearest_interp", {"X": sgn((1, 2, 2, 2), 255)},
     {"out_h": 4, "out_w": 4},
     ref=lambda ins: [np.repeat(np.repeat(ins["X"], 2, 2), 2, 3)])
spec("bilinear_interp", {"X": sgn((1, 2, 3, 3), 256)},
     {"out_h": 6, "out_w": 6})
spec("conv3d_transpose",
     {"Input": sgn((1, 2, 3, 3, 3), 257), "Filter": sgn((2, 3, 1, 1, 1),
                                                        258)},
     ref=lambda ins: [np.einsum("bidhw,iodhw->bodhw",
                                ins["Input"], ins["Filter"])])
spec("affine_grid", {"Theta": sgn((2, 2, 3), 259)},
     {"output_shape_attr": (2, 1, 3, 3)}, grad=["Theta"],
     max_rel=0.05)  # exact-linear op; fp32 FD noise dominates
spec("mean_iou",
     {"Predictions": np.array([[0, 1, 2, 1]], np.int64),
      "Labels": np.array([[0, 1, 1, 1]], np.int64)},
     {"num_classes": 3},
     ref=lambda ins: [np.float32((1.0 + 2.0 / 3.0 + 0.0) / 3),
                      None, None],
     n_outputs=3)
spec("fsp", {"X": sgn((2, 3, 2, 2), 260), "Y": sgn((2, 4, 2, 2), 261)},
     ref=lambda ins: [np.einsum("bihw,bjhw->bij", ins["X"],
                                ins["Y"]) / 4.0])


def _conv_shift_ref(ins):
    x, y = ins["X"], ins["Y"]
    B, N = x.shape
    M = y.shape[1]
    half = M // 2
    out = np.zeros_like(x)
    for j in range(M):
        out += np.roll(x, half - j, axis=1) * y[:, j:j + 1]
    return [out]


spec("conv_shift", {"X": sgn((2, 6), 262), "Y": sgn((2, 3), 263)},
     ref=_conv_shift_ref)


def _row_conv_ref(ins):
    x, f = ins["X"], ins["Filter"]
    out = np.zeros_like(x)
    for j in range(f.shape[0]):
        shifted = np.zeros_like(x)
        shifted[:, :x.shape[1] - j] = x[:, j:]
        out += shifted * f[j]
    return [out]


spec("row_conv", {"X": sgn((2, 5, 3), 264), "Filter": sgn((2, 3), 265)},
     ref=_row_conv_ref)
spec("im2sequence", {"X": sgn((1, 2, 4, 4), 266)},
     {"kernels": (2, 2), "strides": (2, 2)})
spec("add_position_encoding", {"X": sgn((2, 4, 6), 267)},
     {"alpha": 1.0, "beta": 0.5})
spec("cvm", {"X": sgn((3, 5), 268), "CVM": sgn((3, 2), 269)},
     {"use_cvm": True}, ref=lambda ins: [ins["X"]])


# --- v1 aliases -------------------------------------------------------
spec("reshape", {"X": sgn((2, 6), 270)}, {"shape": (3, 4)},
     ref=lambda ins: [ins["X"].reshape(3, 4)])
spec("transpose", {"X": sgn((2, 3), 271)}, {"axis": (1, 0)},
     ref=lambda ins: [ins["X"].T])
spec("squeeze", {"X": sgn((2, 1, 3), 272)}, {"axes": (1,)},
     ref=lambda ins: [ins["X"].reshape(2, 3)])
spec("unsqueeze", {"X": sgn((2, 3), 273)}, {"axes": (0,)},
     ref=lambda ins: [ins["X"][None]])
spec("flatten", {"X": sgn((2, 3, 4), 274)}, {"axis": 1},
     ref=lambda ins: [ins["X"].reshape(2, 12)])
spec("fill_zeros_like2", {"X": sgn((2, 3), 275)},
     ref=lambda ins: [np.zeros((2, 3), np.float32)])
spec("fill", {}, {"shape": (2, 2), "value": 1.5},
     ref=lambda ins: [np.full((2, 2), 1.5, np.float32)])
spec("minus", {"X": sgn((2, 3), 276), "Y": sgn((2, 3), 277)},
     ref=lambda ins: [ins["X"] - ins["Y"]])
spec("cross_entropy2",
     {"X": u((3, 4), 278, lo=0.1, hi=0.3),
      "Label": np.array([[0], [2], [3]], np.int64)},
     ref=lambda ins: [-np.log(np.take_along_axis(
         ins["X"], np.array([[0], [2], [3]]), axis=1)), None],
     n_outputs=2)
spec("gaussian_random_batch_size_like",
     {"Input": sgn((4, 2), 279)}, {"shape": (1, 3)},
     custom="batch_size_like_normal")
spec("uniform_random_batch_size_like",
     {"Input": sgn((5, 2), 280)},
     {"shape": (1, 3), "min": -1.0, "max": 1.0},
     custom="batch_size_like_uniform")


def _seq_conv_ref(ins, ctx=3):
    x, f = ins["X"], ins["Filter"]
    B, T, D = x.shape
    start = -((ctx - 1) // 2)
    out = np.zeros((B, T, f.shape[1]), np.float32)
    for b in range(B):
        for t in range(T):
            row = []
            for j in range(ctx):
                tt = t + start + j
                row.append(x[b, tt] if 0 <= tt < T
                           else np.zeros(D, np.float32))
            out[b, t] = np.concatenate(row) @ f
    return [out]


spec("lstmp",
     {"Input": sgn((2, 3, 16), 290), "Weight": sgn((3, 16), 291),
      "ProjWeight": sgn((4, 3), 292), "Bias": sgn((16,), 293)},
     grad=["Input", "Weight", "ProjWeight", "Bias"], n_outputs=4,
     max_rel=0.03)  # deep tanh chains: fp32 FD noise compounds
spec("sequence_conv",
     {"X": sgn((2, 4, 3), 281), "Filter": sgn((9, 5), 282)},
     {"context_length": 3}, ref=_seq_conv_ref)
spec("sequence_reshape", {"X": sgn((2, 4, 6), 283)}, {"new_dim": 8},
     ref=lambda ins: [ins["X"].reshape(2, 3, 8), None], n_outputs=2)
spec("sequence_scatter",
     {"X": sgn((2, 6), 284), "Ids": np.array([[0, 2], [5, 5]], np.int64),
      "Updates": sgn((2, 2), 285),
      "Lengths": np.array([2, 1], np.int64)},
     ref=lambda ins: [_seq_scatter_ref(ins)], grad=["X", "Updates"])


def _seq_scatter_ref(ins):
    out = ins["X"].copy()
    out[0, 0] += ins["Updates"][0, 0]
    out[0, 2] += ins["Updates"][0, 1]
    out[1, 5] += ins["Updates"][1, 0]
    return out


def _psroi_ref(ins, co=2, ph=2, pw=2):
    x, rois = ins["X"], ins["ROIs"]
    out = np.zeros((len(rois), co, ph, pw), np.float32)
    for r, roi in enumerate(rois):
        x1, y1, x2, y2 = [int(round(v)) for v in roi]
        bh = (y2 - y1) / ph
        bw = (x2 - x1) / pw
        for c in range(co):
            for i in range(ph):
                for j in range(pw):
                    ch = c * ph * pw + i * pw + j
                    r1 = int(np.floor(y1 + i * bh))
                    r2 = int(np.floor(y1 + (i + 1) * bh))
                    c1 = int(np.floor(x1 + j * bw))
                    c2 = int(np.floor(x1 + (j + 1) * bw))
                    region = x[0, ch, r1:r2, c1:c2]
                    out[r, c, i, j] = region.mean()
    return [out]


spec("psroi_pool",
     {"X": sgn((1, 8, 8, 8), 295),
      "ROIs": np.array([[0.0, 0.0, 8.0, 8.0],
                        [0.0, 4.0, 4.0, 8.0]], np.float32),
      "RoisBatchIdx": np.array([0, 0], np.int32)},
     {"output_channels": 2, "pooled_height": 2, "pooled_width": 2,
      "spatial_scale": 1.0},
     ref=_psroi_ref, grad=["X"], max_rel=0.02)


def _dconv_ref(ins):
    """zero offsets + unit mask == plain 3x3 valid conv."""
    x, w = ins["Input"], ins["Filter"]
    N, C, H, W = x.shape
    Co, _, kh, kw = w.shape
    Ho, Wo = H - kh + 1, W - kw + 1
    out = np.zeros((N, Co, Ho, Wo), np.float32)
    for i in range(Ho):
        for j in range(Wo):
            patch = x[:, :, i:i + kh, j:j + kw]
            out[:, :, i, j] = np.einsum("nchw,ochw->no", patch, w)
    return [out]


spec("deformable_conv",
     {"Input": sgn((1, 2, 5, 5), 296),
      "Offset": np.zeros((1, 18, 3, 3), np.float32),
      "Mask": np.ones((1, 9, 3, 3), np.float32),
      "Filter": sgn((2, 2, 3, 3), 297)},
     ref=_dconv_ref, grad=["Input", "Filter"], max_rel=0.02)
spec("deformable_conv",
     {"Input": u((1, 2, 5, 5), 298),
      "Offset": u((1, 18, 3, 3), 299, lo=0.2, hi=0.4),
      "Mask": u((1, 9, 3, 3), 300, lo=0.5, hi=0.9),
      "Filter": sgn((2, 2, 3, 3), 301)},
     grad=["Offset", "Mask"], max_rel=0.02)


spec("roi_perspective_transform",
     {"X": sgn((1, 2, 8, 8), 302),
      "ROIs": np.array([[1, 1, 5, 1, 5, 5, 1, 5],
                        [0, 0, 7, 1, 6, 6, 1, 7]], np.float32),
      "RoisBatchIdx": np.array([0, 0], np.int32)},
     {"transformed_height": 4, "transformed_width": 4,
      "spatial_scale": 1.0},
     grad=["X"], max_rel=0.02)


def _tree_conv_ref(ins, max_depth=2):
    """INDEPENDENT hand-derived eta for the fixture tree
    1->(2,3), 2->4 with max_depth=2 (reference tree2col.h formulas):
    each root's patch = root(depth 0) + children(depth 1);
    eta_t(d)= (2-d)/2; child i of sz sibs: temp=(i-1)/(sz-1) or 0.5.
    Node 5 (N > node_count) is PADDING: its row must be all zero."""
    nodes, filt = ins["NodesVector"], ins["Filter"]
    B, N, F = nodes.shape
    eta = np.zeros((1, N, N, 3), np.float32)
    # roots' self-entries: depth 0 -> (l, r, t) = (0, 0, 1)
    for u in range(4):
        eta[0, u, u] = (0.0, 0.0, 1.0)
    # root 1: children 2 (index 1 of 2) and 3 (index 2 of 2), depth 1
    # eta_t=.5; note eta_r=(1-eta_t)*(1-eta_l) uses the FULL eta_l:
    # node 2: temp 0 -> l=0,   r=.5*(1-0)=.5
    # node 3: temp 1 -> l=.5,  r=.5*(1-.5)=.25
    eta[0, 0, 1] = (0.0, 0.5, 0.5)
    eta[0, 0, 2] = (0.5, 0.25, 0.5)
    # root 2: child 4 (index 1 of 1): temp=.5 -> l=(1-.5)*.5=.25,
    # r=(1-eta_t)*(1-eta_l)=(.5)*(1-.25)=.375
    eta[0, 1, 3] = (0.25, 0.375, 0.5)
    patch = np.einsum("buvc,bvf->bufc", eta, nodes)
    return [np.einsum("bufc,fcok->buok", patch, filt)]


spec("tree_conv",
     {"NodesVector": sgn((1, 5, 3), 303),  # node 5 = padding
      "EdgeSet": np.array([[[1, 2], [1, 3], [2, 4], [0, 0]]],
                          np.int32),
      "Filter": sgn((3, 3, 2, 2), 304)},
     {"max_depth": 2}, ref=_tree_conv_ref,
     grad=["NodesVector", "Filter"], max_rel=0.02)



# --- round-4 EXEMPT conversions: numeric refs for rnn / attention /
# metrics / ema / detection / quant ops (VERDICT r3 item 4) ----------------

def _np_sig(z):
    return 1.0 / (1.0 + np.exp(-z))


def _np_softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


def _lstm_ref(ins):
    x, w, b = ins["Input"], ins["Weight"], ins["Bias"]
    B, T, H4 = x.shape
    H = H4 // 4
    h = np.zeros((B, H), np.float32)
    c = np.zeros((B, H), np.float32)
    bg = b.reshape(-1)[:4 * H]
    hs, cs = [], []
    for t in range(T):
        g = x[:, t] + h @ w + bg
        gi, gf, gc, go = np.split(g, 4, axis=1)
        c = _np_sig(gf) * c + _np_sig(gi) * np.tanh(gc)
        h = _np_sig(go) * np.tanh(c)
        hs.append(h)
        cs.append(c)
    return [np.stack(hs, 1), np.stack(cs, 1), h, c]


spec("lstm",
     {"Input": sgn((2, 3, 8), 910) * 0.5,
      "Weight": sgn((2, 8), 911) * 0.4, "Bias": sgn((1, 8), 912) * 0.2},
     {"use_peepholes": False},
     ref=_lstm_ref, n_outputs=1, max_rel=0.01)


def _gru_ref(ins):
    x, w, b = ins["Input"], ins["Weight"], ins["Bias"]
    B, T, H3 = x.shape
    H = H3 // 3
    h = np.zeros((B, H), np.float32)
    b = b.reshape(-1)
    w_ur, w_c = w[:, :2 * H], w[:, 2 * H:]
    hs = []
    for t in range(T):
        ur = _np_sig(x[:, t, :2 * H] + h @ w_ur + b[:2 * H])
        u, r = ur[:, :H], ur[:, H:]
        c = np.tanh(x[:, t, 2 * H:] + (r * h) @ w_c + b[2 * H:])
        h = (1.0 - u) * h + u * c
        hs.append(h)
    return [np.stack(hs, 1), h]


spec("gru",
     {"Input": sgn((2, 3, 6), 913) * 0.5,
      "Weight": sgn((2, 6), 914) * 0.4, "Bias": sgn((1, 6), 915) * 0.2},
     {}, ref=_gru_ref, max_rel=0.01)


def _attn_ref(ins):
    q, k, v = ins["Q"], ins["K"], ins["V"]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * 0.5
    return [np.einsum("bhqk,bhkd->bhqd", _np_softmax(s), v)]


# no ambient mesh in the sweep -> both fall back to exact full
# attention (the sp-mesh path is covered by test_seq_parallel.py and
# the driver dryrun's sp section)
spec("ring_attention",
     {"Q": sgn((1, 2, 4, 3), 916) * 0.4,
      "K": sgn((1, 2, 4, 3), 917) * 0.4,
      "V": sgn((1, 2, 4, 3), 918) * 0.4},
     {"scale": 0.5}, ref=_attn_ref, max_rel=0.01)
spec("ulysses_attention",
     {"Q": sgn((1, 2, 4, 3), 919) * 0.4,
      "K": sgn((1, 2, 4, 3), 920) * 0.4,
      "V": sgn((1, 2, 4, 3), 921) * 0.4},
     {"scale": 0.5}, ref=_attn_ref, max_rel=0.01)


def _causal_attn_ref(ins):
    q, k, v = ins["Q"], ins["K"], ins["V"]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * 0.5
    sq, sk = s.shape[-2], s.shape[-1]
    mask = np.tril(np.ones((sq, sk), bool))
    s = np.where(mask, s, -1e30)
    return [np.einsum("bhqk,bhkd->bhqd", _np_softmax(s), v)]


spec("zigzag_attention",
     {"Q": sgn((1, 2, 4, 3), 928) * 0.4,
      "K": sgn((1, 2, 4, 3), 929) * 0.4,
      "V": sgn((1, 2, 4, 3), 930) * 0.4},
     {"scale": 0.5}, ref=_causal_attn_ref, max_rel=0.01)


def _moe_ref(ins):
    """Per-token oracle of the Switch top-1 routing (no-drop cf)."""
    x, gw = ins["X"], ins["GateW"]
    w1, b1, w2, b2 = ins["W1"], ins["B1"], ins["W2"], ins["B2"]
    E = w1.shape[0]
    z = x @ gw
    p = np.exp(z - z.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    idx = p.argmax(-1)
    out = np.stack([
        (np.maximum(x[i] @ w1[e] + b1[e], 0.0) @ w2[e] + b2[e])
        * p[i, e]
        for i, e in enumerate(idx)])
    f = np.eye(E)[idx].mean(0)
    aux = E * float((f * p.mean(0)).sum())
    return [out.astype(np.float32), np.float32(aux)]


# continuous inputs: sgn()'s +-1 grid creates router-logit TIES whose
# argmax flips under finite-difference perturbation (discrete routing
# is non-differentiable at ties; away from them the grads are exact)
spec("moe_ffn",
     {"X": u((6, 4), 922, lo=-0.9, hi=0.9),
      "GateW": u((4, 2), 923, lo=-1.0, hi=1.0),
      "W1": u((2, 4, 8), 924, lo=-0.3, hi=0.3),
      "B1": u((2, 8), 925, lo=-0.1, hi=0.1),
      "W2": u((2, 8, 4), 926, lo=-0.3, hi=0.3),
      "B2": u((2, 4), 927, lo=-0.1, hi=0.1)},
     {"capacity_factor": 2.0}, ref=_moe_ref, n_outputs=2,
     # FD grads only on the post-routing smooth slots: X/GateW/W1
     # cross the argmax routing boundary and the relu kink under
     # perturbation (discrete routing is non-differentiable at
     # flips); full analytic-grad equality sharded-vs-reference is
     # tests/test_moe.py::test_sharded_gradients_match
     grad=["W2", "B2"], max_rel=0.02)


def _seq_expand_ref(ins):
    x, y, ln = ins["X"], ins["Y"], ins["SeqLenY"]
    out = np.repeat(x[:, None], y.shape[1], axis=1).astype(np.float32)
    for b_, n_ in enumerate(ln):
        out[b_, int(n_):] = 0.0
    return [out]


spec("sequence_expand",
     {"X": sgn((2, 3), 922), "Y": u((2, 4, 3), 923),
      "SeqLenY": np.array([4, 2], np.int64)},
     {}, ref=_seq_expand_ref)
spec("sequence_expand_as",
     {"X": sgn((2, 3), 924), "Y": u((2, 4, 3), 925),
      "SeqLenY": np.array([3, 4], np.int64)},
     {}, ref=_seq_expand_ref)

spec("assign_numpy_value", {},
     {"_value": np.arange(6, dtype=np.float32).reshape(2, 3),
      "dtype": "float32"},
     ref=lambda ins: [np.arange(6, dtype=np.float32).reshape(2, 3)])


def _beam_search_ref(ins):
    pre_ids, pre_scores, scores = (ins["PreIds"], ins["PreScores"],
                                   ins["Scores"])
    B, K, V = scores.shape
    total = pre_scores[..., None] + scores
    finished = pre_ids == 0  # end_id 0
    neg_inf = np.finfo(np.float32).min
    for b_ in range(B):
        for k_ in range(K):
            if finished[b_, k_]:
                row = np.full(V, neg_inf, np.float32)
                row[0] = pre_scores[b_, k_]
                total[b_, k_] = row
    flat = total.reshape(B, K * V)
    idx = np.argsort(-flat, axis=1)[:, :K]
    sel = np.take_along_axis(flat, idx, axis=1)
    return [(idx % V).astype(np.int64), sel,
            (idx // V).astype(np.int32)]


spec("beam_search",
     {"PreIds": np.array([[1, 2]], np.int64),
      "PreScores": np.array([[-0.5, -0.9]], np.float32),
      "Scores": (sgn((1, 2, 4), 926) * 2).astype(np.float32)},
     {"beam_size": 2, "end_id": 0}, ref=_beam_search_ref)

spec("ema_update",
     {"Param": u((2, 3), 927), "Ema": u((2, 3), 928),
      "DecayPow": np.array([0.5], np.float32)},
     {"decay": 0.9},
     ref=lambda ins: [0.9 * ins["Ema"] + 0.1 * ins["Param"],
                      ins["DecayPow"] * 0.9],
     n_outputs=1)


def _avg_acc_ref(ins):
    s1 = ins["Sum1"] + ins["Param"]
    nu = ins["NumUpdates"] + 1
    na = ins["NumAccumulates"] + 1
    return [s1, ins["Sum2"], ins["Sum3"], na,
            ins["OldNumAccumulates"], nu]


spec("average_accumulates",
     {"Param": u((2, 3), 929), "Sum1": u((2, 3), 930),
      "Sum2": u((2, 3), 931), "Sum3": np.zeros((2, 3), np.float32),
      "NumAccumulates": np.array([3], np.int64),
      "OldNumAccumulates": np.array([0], np.int64),
      "NumUpdates": np.array([3], np.int64)},
     {"average_window": 0.0, "min_average_window": 10000,
      "max_average_window": 10000},
     ref=_avg_acc_ref)

spec("accuracy",
     {"Out": u((4, 2), 932),
      "Indices": np.array([[1, 0], [2, 3], [0, 1], [2, 0]], np.int64),
      "Label": np.array([[1], [0], [2], [2]], np.int64)},
     {},
     ref=lambda ins: [np.float32(0.5), np.float32(2.0),
                      np.float32(4.0)])


def _auc_ref(ins, num_thresholds=7):
    pred, lab = ins["Predict"].reshape(-1), ins["Label"].reshape(-1)
    pos = ins["StatPos"].copy()
    neg = ins["StatNeg"].copy()
    bucket = np.clip((pred * num_thresholds).astype(np.int64), 0,
                     num_thresholds)
    for b_, l_ in zip(bucket, lab):
        if l_ > 0:
            pos[b_] += 1
        else:
            neg[b_] += 1
    tp = np.cumsum(pos[::-1])
    fp = np.cumsum(neg[::-1])
    tp_prev = np.concatenate([[0.0], tp[:-1]])
    fp_prev = np.concatenate([[0.0], fp[:-1]])
    area = np.sum((fp - fp_prev) * (tp + tp_prev) / 2.0)
    denom = tp[-1] * fp[-1]
    return [np.float32(area / denom if denom > 0 else 0.0), pos, neg]


spec("auc",
     {"Predict": np.array([[0.1], [0.9], [0.6], [0.3]], np.float32),
      "Label": np.array([[0], [1], [1], [0]], np.int64),
      "StatPos": np.zeros(8, np.float32),
      "StatNeg": np.zeros(8, np.float32)},
     {"num_thresholds": 7}, ref=_auc_ref)


def _pr_ref(ins, class_number=3):
    lab = ins["Labels"].reshape(-1)
    pred = ins["Indices"].reshape(-1)
    ids = np.arange(class_number)
    tp = ((pred[:, None] == ids) & (lab[:, None] == ids)).sum(0)
    fp = ((pred[:, None] == ids) & (lab[:, None] != ids)).sum(0)
    fn = ((pred[:, None] != ids) & (lab[:, None] == ids)).sum(0)
    batch = np.stack([tp, fp, fn], 1).astype(np.float32)
    accum = ins["StatesInfo"] + batch

    def metrics(s):
        tp_, fp_, fn_ = s[:, 0], s[:, 1], s[:, 2]
        prec = tp_ / np.maximum(tp_ + fp_, 1.0)
        rec = tp_ / np.maximum(tp_ + fn_, 1.0)
        f1 = 2 * prec * rec / np.maximum(prec + rec, 1e-6)
        return np.array([prec.mean(), rec.mean(), f1.mean(),
                         prec.mean(), rec.mean(), f1.mean()],
                        np.float32)

    return [metrics(batch), metrics(accum), accum]


spec("precision_recall",
     {"MaxProbs": u((5, 1), 933),
      "Indices": np.array([[0], [1], [2], [1], [0]], np.int64),
      "Labels": np.array([[0], [1], [1], [2], [0]], np.int64),
      "StatesInfo": np.ones((3, 3), np.float32)},
     {"class_number": 3}, ref=_pr_ref)


# --- detection geometry ----------------------------------------------------

def _prior_box_ref(ins):
    feat_h, feat_w = ins["Input"].shape[2:]
    img_h, img_w = ins["Image"].shape[2:]
    min_sizes, max_sizes = [4.0], [8.0]
    ars = [1.0, 2.0, 0.5]  # flip=True over (2.0,)
    sw, sh = img_w / feat_w, img_h / feat_h
    whs = []
    for ms in min_sizes:
        for ar in ars:
            whs.append((ms * ar ** 0.5, ms / ar ** 0.5))
        big = (ms * max_sizes[0]) ** 0.5
        whs.append((big, big))
    wh = np.array(whs, np.float32)
    boxes = np.zeros((feat_h, feat_w, len(whs), 4), np.float32)
    for i in range(feat_h):
        for j in range(feat_w):
            cx, cy = (j + 0.5) * sw, (i + 0.5) * sh
            boxes[i, j] = np.stack(
                [(cx - wh[:, 0] / 2) / img_w, (cy - wh[:, 1] / 2) / img_h,
                 (cx + wh[:, 0] / 2) / img_w, (cy + wh[:, 1] / 2) / img_h],
                -1)
    var = np.broadcast_to(
        np.array([0.1, 0.1, 0.2, 0.2], np.float32), boxes.shape)
    return [boxes, var.copy()]


spec("prior_box",
     {"Input": u((1, 2, 2, 3), 934), "Image": u((1, 3, 16, 12), 935)},
     {"min_sizes": (4.0,), "max_sizes": (8.0,),
      "aspect_ratios": (2.0,), "flip": True},
     ref=_prior_box_ref)


def _density_prior_ref(ins):
    feat_h, feat_w = ins["Input"].shape[2:]
    img_h, img_w = ins["Image"].shape[2:]
    sw, sh = img_w / feat_w, img_h / feat_h
    entries = []
    size, dens = 4.0, 2
    for ar in (1.0,):
        bw = size * ar ** 0.5
        bh = size / ar ** 0.5
        shift = size / dens
        for di in range(dens):
            for dj in range(dens):
                ox = -size / 2 + shift / 2 + dj * shift
                oy = -size / 2 + shift / 2 + di * shift
                entries.append((ox, oy, bw, bh))
    ent = np.array(entries, np.float32)
    boxes = np.zeros((feat_h, feat_w, len(ent), 4), np.float32)
    for i in range(feat_h):
        for j in range(feat_w):
            ccx = (j + 0.5) * sw + ent[:, 0]
            ccy = (i + 0.5) * sh + ent[:, 1]
            boxes[i, j] = np.stack(
                [(ccx - ent[:, 2] / 2) / img_w,
                 (ccy - ent[:, 3] / 2) / img_h,
                 (ccx + ent[:, 2] / 2) / img_w,
                 (ccy + ent[:, 3] / 2) / img_h], -1)
    var = np.broadcast_to(
        np.array([0.1, 0.1, 0.2, 0.2], np.float32), boxes.shape)
    return [boxes, var.copy()]


spec("density_prior_box",
     {"Input": u((1, 2, 2, 2), 936), "Image": u((1, 3, 16, 16), 937)},
     {"densities": (2,), "fixed_sizes": (4.0,), "fixed_ratios": (1.0,)},
     ref=_density_prior_ref)


def _anchor_gen_ref(ins):
    feat_h, feat_w = ins["Input"].shape[2:]
    sw = sh = 16.0
    whs = []
    for ar in (0.5, 1.0):
        for size in (32.0, 64.0):
            area = sw * sh
            base_w = round((area / ar) ** 0.5)
            base_h = round(base_w * ar)
            whs.append((size / sw * base_w, size / sh * base_h))
    wh = np.array(whs, np.float32)
    anchors = np.zeros((feat_h, feat_w, len(whs), 4), np.float32)
    for i in range(feat_h):
        for j in range(feat_w):
            cx, cy = (j + 0.5) * sw, (i + 0.5) * sh
            anchors[i, j] = np.stack(
                [cx - wh[:, 0] / 2, cy - wh[:, 1] / 2,
                 cx + wh[:, 0] / 2, cy + wh[:, 1] / 2], -1)
    var = np.broadcast_to(
        np.array([0.1, 0.1, 0.2, 0.2], np.float32), anchors.shape)
    return [anchors, var.copy()]


spec("anchor_generator", {"Input": u((1, 2, 2, 2), 938)},
     {"anchor_sizes": (32.0, 64.0), "aspect_ratios": (0.5, 1.0),
      "stride": (16.0, 16.0)},
     ref=_anchor_gen_ref)


def _bipartite_ref(ins):
    dist = ins["DistMat"].copy()
    B, N, M = dist.shape
    midx = np.full((B, M), -1, np.int32)
    mdist = np.zeros((B, M), np.float32)
    for b_ in range(B):
        d = dist[b_].copy()
        for _ in range(min(N, M)):
            i, j = np.unravel_index(np.argmax(d), d.shape)
            if d[i, j] <= 0:
                continue
            midx[b_, j] = i
            mdist[b_, j] = d[i, j]
            d[i, :] = -1.0
            d[:, j] = -1.0
    return [midx, mdist]


spec("bipartite_match",
     {"DistMat": np.array(
         [[[0.9, 0.2, 0.1], [0.3, 0.8, 0.05]],
          [[0.1, 0.6, 0.4], [0.7, 0.2, 0.3]]], np.float32)},
     {}, ref=_bipartite_ref)


def _mine_hard_ref(ins):
    loss = ins["ClsLoss"] + ins["LocLoss"]
    mi, md = ins["MatchIndices"], ins["MatchDist"]
    is_neg = (mi < 0) & (md < 0.5)
    sel = np.zeros_like(mi)
    for b_ in range(mi.shape[0]):
        limit = (mi[b_] >= 0).sum() * 3.0
        neg_losses = np.where(is_neg[b_], loss[b_], -np.inf)
        order = np.argsort(-neg_losses, kind="stable")
        ranks = np.argsort(order, kind="stable")
        sel[b_] = (is_neg[b_] & (ranks < limit)).astype(np.int32)
    return [sel, mi]


spec("mine_hard_examples",
     {"ClsLoss": u((1, 5), 939), "LocLoss": u((1, 5), 940),
      "MatchIndices": np.array([[0, -1, -1, -1, -1]], np.int32),
      "MatchDist": np.array([[0.9, 0.1, 0.2, 0.1, 0.6]], np.float32)},
     {"neg_pos_ratio": 3.0, "neg_dist_threshold": 0.5},
     ref=_mine_hard_ref)


def _mcnms_ref(ins):
    # 1 image, bg class 0 + 1 real class, 3 shared boxes; box 1
    # overlaps box 0 above the 0.3 IoU threshold -> suppressed
    return [np.array([[[1.0, 0.9, 0.0, 0.0, 10.0, 10.0],
                       [1.0, 0.7, 20.0, 20.0, 30.0, 30.0],
                       [-1.0, -1.0, -1.0, -1.0, -1.0, -1.0]]],
                     np.float32),
            np.array([2], np.int32)]


spec("multiclass_nms",
     {"BBoxes": np.array([[[0.0, 0.0, 10.0, 10.0],
                           [0.0, 0.0, 9.5, 9.8],
                           [20.0, 20.0, 30.0, 30.0]]], np.float32),
      "Scores": np.array([[[0.05, 0.05, 0.05],
                           [0.9, 0.8, 0.7]]], np.float32)},
     {"background_label": 0, "score_threshold": 0.1,
      "nms_threshold": 0.3},
     ref=_mcnms_ref)


def _gen_props_ref(ins):
    # zero deltas decode back to the anchors; disjoint anchors -> no
    # NMS suppression; ranked by score
    return [np.array([[[8.0, 8.0, 15.0, 15.0],
                       [0.0, 0.0, 5.0, 5.0]]], np.float32),
            np.array([[0.9, 0.8]], np.float32),
            np.array([2], np.int32)]


spec("generate_proposals",
     {"Scores": np.array([[[[0.8]], [[0.9]]]], np.float32),
      "BboxDeltas": np.zeros((1, 8, 1, 1), np.float32),
      "ImInfo": np.array([[20.0, 20.0, 1.0]], np.float32),
      "Anchors": np.array([[[[0.0, 0.0, 5.0, 5.0],
                             [8.0, 8.0, 15.0, 15.0]]]], np.float32),
      "Variances": np.ones((1, 1, 2, 4), np.float32)},
     {"pre_nms_top_n": 6000, "post_nms_top_n": 2, "nms_thresh": 0.5,
      "min_size": 0.1},
     ref=_gen_props_ref)


def _rpn_ta_ref(ins):
    # hand-walked: a0 matches gt exactly (fg), a1/a3 are clean bg,
    # a2 sits between the thresholds (ignored); quotas don't bind
    loc = np.array([[0, 1, 3, -1]], np.int32)
    lbl = np.array([[1, 0, 0, -1]], np.int32)
    tgt = np.zeros((1, 4, 4), np.float32)
    w = np.zeros((1, 4, 4), np.float32)
    w[0, 0] = 1.0
    return [loc, loc, lbl, tgt, w]


spec("rpn_target_assign",
     {"Anchor": np.array([[0.0, 0.0, 9.0, 9.0],
                          [30.0, 30.0, 39.0, 39.0],
                          [0.0, 0.0, 19.0, 9.0],
                          [40.0, 40.0, 45.0, 45.0]], np.float32),
      "GtBoxes": np.array([[[0.0, 0.0, 9.0, 9.0],
                            [0.0, 0.0, 0.0, 0.0]]], np.float32),
      "IsCrowd": np.zeros((1, 2), np.int32),
      "ImInfo": np.array([[50.0, 50.0, 1.0]], np.float32)},
     {"rpn_batch_size_per_im": 4, "rpn_fg_fraction": 0.5,
      "rpn_positive_overlap": 0.7, "rpn_negative_overlap": 0.3,
      "use_random": False},
     ref=_rpn_ta_ref)


def _bda_ref(ins):
    pb, var, tb, sc = (ins["PriorBox"], ins["PriorBoxVar"],
                       ins["TargetBox"], ins["BoxScore"])
    r, cnum = sc.shape
    pw = pb[:, 2] - pb[:, 0] + 1.0
    ph = pb[:, 3] - pb[:, 1] + 1.0
    pcx = pb[:, 0] + pw / 2
    pcy = pb[:, 1] + ph / 2
    t = tb.reshape(r, cnum, 4)
    v = var[0]
    clipv = 4.135166556742356
    dx, dy = t[..., 0] * v[0], t[..., 1] * v[1]
    dw = np.clip(t[..., 2] * v[2], -clipv, clipv)
    dh = np.clip(t[..., 3] * v[3], -clipv, clipv)
    cx = dx * pw[:, None] + pcx[:, None]
    cy = dy * ph[:, None] + pcy[:, None]
    w = np.exp(dw) * pw[:, None]
    h = np.exp(dh) * ph[:, None]
    dec = np.stack([cx - w / 2, cy - h / 2,
                    cx + w / 2 - 1, cy + h / 2 - 1], -1)
    best = sc.argmax(1)
    assign = dec[np.arange(r), best]
    return [dec.reshape(r, cnum * 4).astype(np.float32),
            assign.astype(np.float32)]


spec("box_decoder_and_assign",
     {"PriorBox": np.array([[0.0, 0.0, 9.0, 9.0],
                            [4.0, 4.0, 11.0, 13.0]], np.float32),
      "PriorBoxVar": np.array([[0.1, 0.1, 0.2, 0.2]], np.float32),
      "TargetBox": sgn((2, 8), 941) * 0.5,
      "BoxScore": u((2, 2), 942)},
     {}, ref=_bda_ref)


def _dfp_ref(ins):
    rois = ins["FpnRois"]
    w = rois[:, 2] - rois[:, 0]
    h = rois[:, 3] - rois[:, 1]
    scale = np.sqrt(np.maximum(w * h, 1e-8))
    lvl = np.clip(np.floor(np.log2(scale / 224.0 + 1e-8)) + 4, 2, 5)
    outs = [np.where((lvl == L)[:, None], rois, 0.0).astype(np.float32)
            for L in range(2, 6)]
    return outs + [np.arange(len(rois), dtype=np.int32)[:, None]]


spec("distribute_fpn_proposals",
     {"FpnRois": np.array([[0, 0, 30, 30], [0, 0, 120, 100],
                           [0, 0, 300, 200], [0, 0, 500, 500]],
                          np.float32)},
     {}, ref=_dfp_ref, n_outputs=4)

spec("collect_fpn_proposals",
     {"MultiLevelRois": [np.array([[0, 0, 5, 5], [1, 1, 6, 6]],
                                  np.float32),
                         np.array([[2, 2, 9, 9]], np.float32)],
      "MultiLevelScores": [np.array([0.9, 0.2], np.float32),
                           np.array([0.5], np.float32)]},
     {"post_nms_topN": 2},
     ref=lambda ins: [np.array([[0, 0, 5, 5], [2, 2, 9, 9]],
                               np.float32)])


def _yolo_box_ref(ins):
    x, img_size = ins["X"], ins["ImgSize"]
    n, _, h, w = x.shape
    anchors, class_num, down = (2, 3), 2, 32
    na = 1
    x = x.reshape(n, na, 5 + class_num, h, w)
    boxes = np.zeros((n, na, h, w, 4), np.float32)
    scores = np.zeros((n, na, h, w, class_num), np.float32)
    for b_ in range(n):
        ih, iw = img_size[b_]
        for i in range(h):
            for j in range(w):
                px = (_np_sig(x[b_, 0, 0, i, j]) + j) / w
                py = (_np_sig(x[b_, 0, 1, i, j]) + i) / h
                pw = np.exp(x[b_, 0, 2, i, j]) * anchors[0] / (down * w)
                ph = np.exp(x[b_, 0, 3, i, j]) * anchors[1] / (down * h)
                conf = _np_sig(x[b_, 0, 4, i, j])
                if conf < 0.01:
                    continue
                x1 = np.clip((px - pw / 2) * iw, 0, iw - 1)
                y1 = np.clip((py - ph / 2) * ih, 0, ih - 1)
                x2 = np.clip((px + pw / 2) * iw, 0, iw - 1)
                y2 = np.clip((py + ph / 2) * ih, 0, ih - 1)
                boxes[b_, 0, i, j] = (x1, y1, x2, y2)
                scores[b_, 0, i, j] = (_np_sig(x[b_, 0, 5:, i, j])
                                       * conf)
    return [boxes.reshape(n, -1, 4), scores.reshape(n, -1, class_num)]


spec("yolo_box",
     {"X": sgn((1, 7, 2, 2), 943),
      "ImgSize": np.array([[64, 64]], np.int64)},
     {"anchors": (2, 3), "class_num": 2},
     ref=_yolo_box_ref)


def _simfocus_ref(ins):
    x = ins["X"]
    n, c, h, w = x.shape
    out = np.zeros_like(x)
    for idx in (0,):
        sl = x[:, idx]
        for b_ in range(n):
            mask = np.zeros((h, w), np.float32)
            for i in range(h):
                mask[i, sl[b_, i].argmax()] = 1.0
            for j in range(w):
                mask[sl[b_, :, j].argmax(), j] = 1.0
            out[b_] += mask[None]
    return [np.minimum(out, 1.0)]


spec("similarity_focus", {"X": u((2, 3, 4, 5), 944)},
     {"axis": 1, "indexes": (0,)}, ref=_simfocus_ref)


# composite losses: analytic-vs-numeric grad check (the ref output is
# the op's own convergence-tested lowering; test_detection.py covers
# end-to-end behavior)
spec("yolov3_loss",
     {"X": sgn((1, 14, 2, 2), 945) * 0.5,
      "GTBox": np.array([[[0.5, 0.5, 0.3, 0.4]]], np.float32),
      "GTLabel": np.array([[1]], np.int64),
      "GTScore": np.ones((1, 1), np.float32)},
     {"anchors": (10, 13, 16, 30), "anchor_mask": (0, 1),
      "class_num": 2, "ignore_thresh": 0.7, "downsample_ratio": 32,
      "use_label_smooth": False},
     grad=["X"], max_rel=0.02)
spec("ssd_loss",
     {"Location": sgn((1, 3, 4), 946) * 0.3,
      "Confidence": sgn((1, 3, 3), 947) * 0.5,
      "GtBox": np.array([[[0.1, 0.1, 0.4, 0.5]]], np.float32),
      "GtLabel": np.array([[1]], np.int64),
      "PriorBox": np.array([[0.1, 0.1, 0.45, 0.5],
                            [0.5, 0.5, 0.9, 0.9],
                            [0.0, 0.6, 0.3, 0.95]], np.float32),
      "PriorBoxVar": np.full((3, 4), 0.1, np.float32)},
     {}, grad=["Location", "Confidence"], max_rel=0.02)


def _fcq_ref(ins):
    x = ins["X"]
    scale = np.abs(x).max(axis=(1,), keepdims=True)
    qmax = 127.0
    s = np.maximum(scale, 1e-8)
    out = np.clip(np.round(x / s * qmax), -qmax, qmax) * s / qmax
    return [out.astype(np.float32), scale.reshape(-1)]


# grad=[]: the STE backward is the identity BY DESIGN (reference
# fake_quantize_op grad passes through), so a finite-difference check
# against the stepped forward is meaningless — output check only
spec("fake_channel_wise_quantize_dequantize_abs_max",
     {"X": sgn((3, 4), 948)}, {"bit_length": 8, "quant_axis": 0},
     ref=_fcq_ref, grad=[])


def _fqma_ref(ins):
    x, in_scale = ins["X"], ins["InScale"]
    cur = np.abs(x).max()
    scale = 0.9 * in_scale + 0.1 * cur if in_scale > 0 else cur
    qmax = 127.0
    s = np.maximum(scale, 1e-8)
    out = np.clip(np.round(x / s * qmax), -qmax, qmax) * s / qmax
    return [out.astype(np.float32), np.float32(scale)]


spec("fake_quantize_dequantize_moving_average_abs_max",
     {"X": sgn((3, 4), 949),
      "InScale": np.array(0.8, np.float32)},
     {"bit_length": 8, "moving_rate": 0.9}, ref=_fqma_ref, grad=[])




def _c2df_ref(ins):
    import torch
    import torch.nn.functional as F
    out = F.conv2d(torch.from_numpy(ins["Input"]),
                   torch.from_numpy(ins["Filter"]),
                   torch.from_numpy(ins["Bias"]).reshape(-1))
    return [out.numpy()]


spec("conv2d_fusion",
     {"Input": sgn((1, 2, 5, 5), 950), "Filter": sgn((3, 2, 3, 3), 951),
      "Bias": sgn((3,), 952)},
     {"strides": (1, 1), "paddings": (0, 0), "activation": ""},
     ref=_c2df_ref, max_rel=0.01)


def _tfc_ref(ins):
    outs = []
    for x in ins["X"]:
        t = np.transpose(x, (0, 2, 3, 1))
        outs.append(t.reshape(t.shape[0], -1))
    return [np.concatenate(outs, axis=1)]


spec("fusion_transpose_flatten_concat",
     {"X": [sgn((2, 3, 2, 2), 953), sgn((2, 3, 4, 4), 954)]},
     {"trans_axis": (0, 2, 3, 1), "flatten_axis": 1,
      "concat_axis": 1},
     ref=_tfc_ref)


def _spc_ref(ins):
    outs = []
    for x, ln in zip(ins["X"], ins["SeqLen"]):
        m = np.zeros_like(x)
        for b_, n_ in enumerate(ln):
            m[b_, :int(n_)] = x[b_, :int(n_)]
        outs.append(m.sum(axis=1))
    return [np.concatenate(outs, axis=1)]


spec("fusion_seqpool_concat",
     {"X": [u((2, 3, 4), 955), u((2, 3, 2), 956)],
      "SeqLen": [np.array([3, 1], np.int64),
                 np.array([2, 3], np.int64)]},
     {"pooltype": "SUM", "axis": 1},
     ref=_spc_ref)


def _fusion_lstm_ref(ins):
    proj = np.einsum("btd,dh->bth", ins["X"], ins["WeightX"])
    return _lstm_ref({"Input": proj, "Weight": ins["WeightH"],
                      "Bias": ins["Bias"]})[:2]


spec("fusion_lstm",
     {"X": sgn((2, 3, 5), 957) * 0.5, "WeightX": sgn((5, 8), 958) * 0.4,
      "WeightH": sgn((2, 8), 959) * 0.4,
      "Bias": sgn((1, 8), 960) * 0.2},
     {"use_peepholes": False}, ref=_fusion_lstm_ref, max_rel=0.01)

EXEMPT = {
    # a chunked scan that writes the step's counters in place; finite
    # differences over a 64-token chunk's triangular inverse say less
    # than the recurrence does
    "kda_attention":
        "test_kda.py (the chunked form against the token-by-token "
        "recurrence, forward and every gradient; the counters), "
        "test_kimi_linear_model.py",
    # discrete routing over persistable buffers (a bias buffer and the
    # step's counters written in place; top-k flips under a finite
    # difference)
    "moe_sigmoid_router":
        "test_afmoe_model.py (choice, weights and counters by hand; "
        "loss and every gradient against the float32 reference)",
    "moe_held_experts":
        "test_afmoe_model.py (every gradient against the reference, "
        "the 16 shares against the uncut layer, overflow), "
        "test_held_experts_chunks.py (loads around the chunks' ends), "
        "test_grouped_matmul.py",
    # host callbacks
    "print": "test_misc_parity.py (host callback, pass-through)",
    "py_func": "test_new_ops.py (host callback + custom backward)",
    # genuinely rng-driven sampling (statistical contracts elsewhere)
    "nce": "test_new_ops.py (rng-sampled negatives)",
    "sampling_id": "test_new_ops.py (rng draw, distribution check)",
    "sample_logits": "test_new_ops.py (rng-sampled classes)",
    "random_crop": "test_new_ops.py (rng offsets)",
    "dgc": "test_average_ema.py (rng top-k sparsification; momentum "
           "parity, sparsity ratio, residual)",
    "generate_proposal_labels":
        "test_detection.py (rng fg/bg subsampling; "
        "TestMaskRCNNTargets quota/targets/determinism)",
    "generate_mask_labels":
        "test_detection.py (rng-paired with proposal sampling; "
        "TestMaskRCNNTargets rasterize + wrappers)",
    # SparseRows containers (not expressible as dense harness feeds)
    "merge_selected_rows": "test_new_ops.py (SparseRows roundtrip)",
    "get_tensor_from_selected_rows":
        "test_new_ops.py (SparseRows roundtrip)",
    # control-flow / tensor-array machinery (take sub-blocks or
    # tensor-array containers, not dense tensors)
    "while": "test_control_flow.py (lax.while/scan lowering + grad)",
    "static_rnn": "test_sequence_rnn.py",
    "dynamic_rnn": "test_sequence_rnn.py",
    "create_array": "test_control_flow.py (tensor arrays)",
    "array_write": "test_control_flow.py",
    "array_read": "test_control_flow.py",
    "array_length": "test_control_flow.py",
    "tensor_array_to_tensor":
        "test_layers_parity.py (tensor-array input; stack/concat "
        "round trip)",
    "beam_search_decode":
        "test_beam_search.py (tensor-array input; backtrack parity)",
}


def _flat_cases():
    cases = []
    for op_type, entries in sorted(SPECS.items()):
        for i, (inputs, attrs, opt) in enumerate(entries):
            cases.append(pytest.param(op_type, inputs, attrs, opt,
                                      id="%s-%d" % (op_type, i)))
    return cases


def _check_random(op_type, attrs, kind):
    """Random ops: statistical contract, not values."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    main = fluid.Program()
    main.random_seed = 1234
    with fluid.program_guard(main):
        from paddle_tpu.layer_helper import LayerHelper
        helper = LayerHelper(op_type)
        out = helper.create_variable_for_type_inference(
            attrs.get("dtype", "float32"), stop_gradient=True)
        helper.append_op(type=op_type, outputs={"Out": [out]},
                         attrs=attrs)
    exe = fluid.Executor()
    (val,) = exe.run(main, feed={}, fetch_list=[out])
    if kind == "random_normal":
        assert val.shape == attrs["shape"]
        assert abs(val.mean()) < 0.5 and 0.5 < val.std() < 1.5
    elif kind == "random_uniform":
        assert (val >= attrs["min"]).all() and \
            (val <= attrs["max"]).all()
    elif kind == "random_truncated":
        assert np.abs(val).max() <= 2.0 * attrs["std"] + 1e-6
    elif kind == "random_int":
        assert np.issubdtype(val.dtype, np.integer)
        assert (val >= attrs["low"]).all() and \
            (val < attrs["high"]).all()
    elif kind == "random_perm":
        assert sorted(val.tolist()) == list(range(attrs["n"]))


def _check_random_with_input(op_type, inputs, attrs, kind):
    """batch_size_like generators: output batch dim copies the ref
    input's; values follow the requested distribution."""
    import paddle_tpu as fluid
    from paddle_tpu.layer_helper import LayerHelper
    main = fluid.Program()
    main.random_seed = 99
    with fluid.program_guard(main):
        ref_np = inputs["Input"]
        x = fluid.layers.data(name="inp", shape=list(ref_np.shape[1:]),
                              dtype="float32")
        helper = LayerHelper(op_type)
        out = helper.create_variable_for_type_inference(
            "float32", stop_gradient=True)
        helper.append_op(type=op_type, inputs={"Input": [x]},
                         outputs={"Out": [out]}, attrs=attrs)
    exe = fluid.Executor()
    (val,) = exe.run(main, feed={"inp": ref_np}, fetch_list=[out])
    expect = (ref_np.shape[0],) + tuple(attrs["shape"][1:])
    assert val.shape == expect, (val.shape, expect)
    if kind == "batch_size_like_uniform":
        assert (val >= attrs["min"]).all() and \
            (val <= attrs["max"]).all()


@pytest.mark.parametrize("op_type,inputs,attrs,opt", _flat_cases())
def test_op(op_type, inputs, attrs, opt):
    opdef = op_registry.get(op_type)
    custom = opt.get("custom")
    if custom:
        if custom.startswith("batch_size_like"):
            _check_random_with_input(op_type, inputs, attrs, custom)
        else:
            _check_random(op_type, attrs, custom)
        return
    ref = opt.get("ref")
    if ref is not None:
        expected = ref(inputs)
        check_output(op_type, inputs, attrs, expected,
                     atol=opt.get("atol", 1e-4),
                     n_outputs=opt.get("n_outputs", 1))
    if not opdef.differentiable:
        return
    grad_slots = opt.get("grad")
    if grad_slots is None:
        grad_slots = [
            s for s, _v in opdef.input_slots
            if s in inputs and s not in opdef.nondiff_slots
            and not isinstance(inputs[s], (list, tuple))
            and np.issubdtype(np.asarray(inputs[s]).dtype,
                              np.floating)]
    if grad_slots:
        check_grad(op_type, inputs, attrs, grad_slots,
                   max_relative_error=opt.get("max_rel", 0.005),
                   output_index=opt.get("out_idx", 0),
                   n_outputs=opt.get("n_outputs", 1),
                   loss_weight=opt.get("loss_weight"))


def test_coverage_ratchet():
    """Every registered op is either swept here or explicitly covered
    by a named test file — new ops can't land untested (the analog of
    the reference's one-test-file-per-op convention)."""
    all_ops = set(op_registry.all_op_types())
    covered = set(SPECS) | set(EXEMPT)
    missing = sorted(all_ops - covered)
    stale = sorted(covered - all_ops)
    assert not missing, "ops with no sweep spec or exemption: %s" \
        % missing
    assert not stale, "specs for unregistered ops: %s" % stale


def test_sweep_scale():
    """The sweep must stay comprehensive: >=180 checked cases and
    every differentiable op accounted for."""
    n_cases = sum(len(v) for v in SPECS.values())
    assert n_cases >= 180, n_cases
    diff_ops = {t for t in op_registry.all_op_types()
                if op_registry.get(t).differentiable}
    unswept = diff_ops - set(SPECS) - set(EXEMPT)
    assert not unswept, sorted(unswept)


def test_op_bench_harness():
    """The per-op microbench (tools/op_bench.py, the op_tester.cc
    analog) runs and compares library variants."""
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import op_bench
    res = op_bench.bench_op(
        "layer_norm",
        {"X": u((8, 16), 300), "Scale": u((16,), 301),
         "Bias": u((16,), 302)}, {}, iters=3, warmup=2)
    libs = {r["library"] for r in res}
    assert libs == {"base", "pallas"}
    assert sum(r["best"] for r in res) == 1
    assert all(r["us_per_call"] > 0 for r in res)


# --- backend-variant rerun (SURVEY §4 item 9: the unittests/mkldnn +
# unittests/ngraph pattern — re-run the SAME numeric specs with the
# alternate kernel library selected) ----------------------------------------

def _variant_cases():
    from paddle_tpu import ops as _ops

    cases = []
    for op_type in sorted(_ops.all_op_types()):
        for lib in sorted(_ops.get(op_type).variants):
            for i, (inputs, attrs, opt) in enumerate(
                    SPECS.get(op_type, [])):
                cases.append(pytest.param(
                    op_type, lib, inputs, attrs, opt,
                    id="%s-%s-%d" % (op_type, lib, i)))
    return cases


@pytest.mark.parametrize("op_type,lib,inputs,attrs,opt",
                         _variant_cases())
def test_op_variant(op_type, lib, inputs, attrs, opt):
    """Every registered kernel VARIANT must pass the op's own numeric
    spec — same refs, same finite-difference grads, alternate
    lowering."""
    from paddle_tpu.core.flags import FLAGS

    prev = FLAGS.op_library
    FLAGS.op_library = "%s:%s" % (op_type, lib)
    try:
        test_op(op_type, inputs, attrs, opt)
    finally:
        FLAGS.op_library = prev


def test_every_variant_op_is_spec_covered():
    """A new pallas variant without a sweep spec would silently skip
    the variant rerun — ratchet it."""
    from paddle_tpu import ops as _ops

    missing = [t for t in _ops.all_op_types()
               if _ops.get(t).variants and t not in SPECS]
    assert not missing, (
        "ops with kernel variants but no sweep spec: %s" % missing)
