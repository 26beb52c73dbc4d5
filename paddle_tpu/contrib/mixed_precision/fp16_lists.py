"""Op lists for automatic mixed precision.

Reference: python/paddle/fluid/contrib/mixed_precision/fp16_lists.py
(AutoMixedPrecisionLists: white/black/gray op sets). The TPU default
low-precision dtype is bfloat16 — same exponent range as float32, so
unlike fp16 the white list can be aggressive (any MXU-bound op)."""

from __future__ import annotations

# Ops whose inputs are cast to the low-precision dtype (MXU-bound:
# matmul/conv dominate FLOPs; bf16 doubles MXU throughput).
white_list = {
    "mul", "matmul", "conv2d", "conv3d", "depthwise_conv2d",
    "conv2d_transpose", "scaled_dot_product_attention",
    # MXU-bound and numerically safe in bf16: all reductions over the
    # vocab axis run in float32 inside the op
    "fused_linear_xent",
    # the grouped products over the held experts; the routing weights
    # and the counters stay float32 (fp16_utils.F32_CONTRACT_*). The
    # router itself (moe_sigmoid_router) is unlisted: its scores are
    # float32 by the model's own statement
    "moe_held_experts",
    # the chunked delta rule's matrix products; the log decay, its
    # cumulative sums, the chunk's triangular inverse and the carried
    # state stay float32 inside (ops/kda_ops.py; fp16_utils.
    # F32_CONTRACT_*)
    "kda_attention",
}

# Numerically sensitive ops that must stay in float32.
black_list = {
    "exp", "log", "square", "softmax", "log_softmax", "mean",
    "cross_entropy",
    "sigmoid_cross_entropy_with_logits", "batch_norm",
    "group_norm", "instance_norm", "reduce_sum", "reduce_mean", "sum",
    "cumsum", "logsumexp", "l2_normalize", "norm", "p_norm",
    "frobenius_norm",
    # the per-channel log decay: exp and softplus of a few thousandths,
    # summed over a chunk by its consumer
    "kda_gate",
}

# Everything else: runs in whatever dtype its inputs arrive in
# (jnp promotion keeps bf16*f32 -> f32).
gray_list = {
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "relu", "gelu", "tanh", "sigmoid", "pool2d",
    "adaptive_pool2d", "transpose2", "reshape2", "concat", "split",
    "slice", "dropout", "scale", "stack", "expand",
    # dtype-preserving movement/identity ops: must not break the
    # low-precision chain (an unlisted op up-casts its inputs)
    "unsqueeze", "squeeze", "unsqueeze2", "squeeze2", "assign",
    "transpose", "reshape", "flatten", "flatten2", "pad", "gather",
    "relu6", "leaky_relu", "clip", "elementwise_max",
    "elementwise_min",
    # layer_norm's lowering computes its statistics in f32 and returns
    # the INPUT dtype (ops/nn_ops.py), so under AMP it can take bf16
    # activations directly — blacklisting it only inserts f32 casts
    # around every LN site (~30 on transformer-base), doubling the
    # inter-fusion buffer traffic for zero numeric gain
    "layer_norm",
    # same contract: softmax_with_cross_entropy computes its
    # statistics in f32 internally whatever the input dtype (loss is
    # always f32), so the [N, V] logits can stay bf16 — halving the
    # head's HBM traffic on BERT-style models
    "softmax_with_cross_entropy",
    # rms_norm keeps layer_norm's contract (float32 inside, the input's
    # type out); the rotation's angles are float32 inside; swish is
    # elementwise
    "rms_norm", "rotary_embedding", "swish",
    # float32 inside, the input's type out
    "short_conv", "gated_rms_norm",
}


class AutoMixedPrecisionLists:
    """Reference: fp16_lists.py AutoMixedPrecisionLists — custom
    white/black sets override the defaults."""

    def __init__(self, custom_white_list=None, custom_black_list=None):
        self.white_list = set(white_list)
        self.black_list = set(black_list)
        self.gray_list = set(gray_list)
        if custom_white_list:
            for op in custom_white_list:
                self.white_list.add(op)
                self.black_list.discard(op)
        if custom_black_list:
            for op in custom_black_list:
                self.black_list.add(op)
                self.white_list.discard(op)
