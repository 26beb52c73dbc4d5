"""Kimi Linear: the hybrid decoder of Moonshot's Kimi-Linear-48B-A3B
(``model_type`` ``kimi_linear``; public ``config.json``, technical
report arXiv:2510.26692), as a causal-LM training graph.

Pre-norm blocks ``h + mixer(norm(h))``, ``h + ffn(norm(h))``. The
mixer of three layers in four is **Kimi Delta Attention** (KDA, layer
kind ``kda``): q, k, v through a causal depthwise short convolution
and SiLU, q and k L2-normalised per head, a per-channel log decay
``g = -exp(A_log) softplus((x W_fa) W_fb + dt_bias)`` and a per-head
``beta = sigmoid(x W_b)`` into the gated delta rule (ops/kda_ops.py),
then an RMSNorm per head times a sigmoid gate and the output
projection. Every fourth layer's mixer is **latent attention without
positions** (MLA, NoPE; layer kind ``mla``): keys and values from a
512-wide latent, the keys' last 64 lanes one vector shared by every
head, queries and keys 192 wide beside 128-wide values, a causal
softmax over the whole row, no rotary anywhere. The mixer is
``models/mla.py latent_attention``, which ``models/deepseek_v3.py``
builds too, there with its rotary part on; this model's published
configuration has none (``mla_use_nope`` true). After the leading dense
layer the FFN is a mixture of experts: a sigmoid top-k router with a
selection-bias buffer, routed experts plus a shared one, as
``models/afmoe.py`` has it (the same two ops).

**One chip's share**, as in ``afmoe``: ``num_experts`` is how many
routed experts THIS program holds (``first_held_expert`` ..) of the
``num_experts_published`` the router scores; what the others would add
is left out.

**The gate's start.** ``A_log`` and ``dt_bias`` start, by the family's
convention, at log uniform(1, 16) and at the inverse softplus of a step
in [0.001, 0.1]. ``kda_gate_start`` gives a fixed spread of those two
ranges (no seed), a constant of the graph; the parameters ``*.A_log`` /
``*.dt_bias`` are added to it and start at nought (``_gate_parameter``),
so a caller that sets every parameter from a generator that cannot
draw such a start (the benchmark's) still starts at the family's gate.

Layer kinds (``name_scope``): ``embedding``, ``kda``, ``mla``, ``ffn``,
``router``, ``experts``, ``shared_expert``, ``residual_norm``,
``vocab_head``, ``loss``.
"""

from __future__ import annotations

import numpy as np

from .. import layers
from ..framework import name_scope
from ..initializer import Constant
from ..param_attr import ParamAttr
from .afmoe import _gated_mlp, _linear, _moe, _norm
from .mla import latent_attention

__all__ = ["KimiLinearConfig", "kimi_linear_lm", "kda_gate_start"]


def published_pattern(n_layers):
    """Every fourth layer full attention, and the last (the public
    config's lists, 1-based)."""
    full = [i for i in range(1, n_layers + 1)
            if i % 4 == 0 or i == n_layers]
    return {"kda_layers": [i for i in range(1, n_layers + 1)
                           if i not in full],
            "full_attn_layers": full}


class KimiLinearConfig:
    """Keys follow the public ``config.json`` (``linear_attn_config``
    nested as there: ``kda_layers`` / ``full_attn_layers`` 1-based,
    ``num_heads``, ``head_dim``, ``short_conv_kernel_size``);
    ``kda_gate_rank`` (the width of the decay's and the output gate's
    low-rank pairs) and ``load_balance_coeff`` (the selection bias's
    step) have no key there; ``seq_len``, ``num_experts_published`` /
    ``first_held_expert`` and ``moe_row_capacity`` are this
    framework's, as ``AfmoeConfig`` has them."""

    def __init__(self, vocab_size=163840, hidden_size=2304,
                 num_hidden_layers=27, first_k_dense_replace=1,
                 linear_attn_config=None, num_attention_heads=32,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, q_lora_rank=None,
                 mla_use_nope=True, intermediate_size=9216,
                 moe_intermediate_size=1024, num_experts=256,
                 num_experts_published=None, first_held_expert=0,
                 num_experts_per_token=8, num_shared_experts=1,
                 routed_scaling_factor=2.446, moe_renormalize=True,
                 moe_router_activation_func="sigmoid",
                 num_expert_group=1, topk_group=1,
                 load_balance_coeff=0.001, kda_gate_rank=128,
                 rms_norm_eps=1e-5, moe_row_capacity=None, seq_len=8192):
        la = dict(published_pattern(num_hidden_layers), num_heads=32,
                  head_dim=128, short_conv_kernel_size=4)
        la.update(linear_attn_config or {})
        kinds = sorted(la["kda_layers"] + la["full_attn_layers"])
        if kinds != list(range(1, num_hidden_layers + 1)):
            raise ValueError(
                "kda_layers %r and full_attn_layers %r do not name each "
                "of %d layers once" % (la["kda_layers"],
                                       la["full_attn_layers"],
                                       num_hidden_layers))
        if q_lora_rank is not None or not mla_use_nope:
            raise ValueError("this model's latent attention has no "
                             "query compression and no rotary "
                             "(q_lora_rank null, mla_use_nope true); "
                             "models/mla.py latent_attention has the "
                             "rotary form, which models/deepseek_v3.py "
                             "builds")
        if moe_router_activation_func != "sigmoid" \
                or num_expert_group != 1 or topk_group != 1:
            raise ValueError("the router here is the sigmoid one over "
                             "one group of experts")
        published = num_experts_published or num_experts
        if first_held_expert + num_experts > published:
            raise ValueError("experts %d..%d of %d" % (
                first_held_expert, first_held_expert + num_experts - 1,
                published))
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.linear_attn_config = la
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.mla_use_nope = mla_use_nope
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_published = published
        self.first_held_expert = first_held_expert
        self.num_experts_per_token = num_experts_per_token
        self.num_shared_experts = num_shared_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.moe_renormalize = moe_renormalize
        self.load_balance_coeff = load_balance_coeff
        self.kda_gate_rank = kda_gate_rank
        self.rms_norm_eps = rms_norm_eps
        self.moe_row_capacity = moe_row_capacity
        self.seq_len = seq_len

    # the router's three keys under the names ``models/afmoe.py``'s
    # ``_moe`` reads them by
    num_experts_per_tok = property(lambda self: self.num_experts_per_token)
    route_scale = property(lambda self: self.routed_scaling_factor)
    route_norm = property(lambda self: self.moe_renormalize)


_GOLDEN = 0.6180339887498949


def kda_gate_start(num_heads, head_dim):
    """(A_log [num_heads], dt_bias [num_heads * head_dim]) float32 at
    the family's start: A = exp(A_log) spread over (1, 16), the step
    dt = softplus(dt_bias) log-spread over (0.001, 0.1), each by the
    fractional parts of multiples of the golden ratio (an even spread
    with no seed)."""
    u = np.mod(np.arange(1, num_heads + 1, dtype=np.float64) * _GOLDEN,
               1.0)
    a_log = np.log(1.0 + 15.0 * u)
    w = np.mod(np.arange(1, num_heads * head_dim + 1, dtype=np.float64)
               * _GOLDEN, 1.0)
    dt = np.exp(np.log(0.001) + w * (np.log(0.1) - np.log(0.001)))
    dt_bias = dt + np.log(-np.expm1(-dt))       # softplus's inverse
    return a_log.astype(np.float32), dt_bias.astype(np.float32)


def _gate_parameter(name, start):
    """``start`` plus a float32 parameter of its shape drawn as
    noughts: training moves the sum as it would move a parameter drawn
    at ``start``."""
    p = layers.create_parameter(start.shape, "float32", name=name,
                                default_initializer=Constant(0.0))
    return layers.elementwise_add(p, layers.assign(start))


@name_scope("kda")
def _kda(a, cfg, prefix):
    la = cfg.linear_attn_config
    h, dh = la["num_heads"], la["head_dim"]
    width, rank = h * dh, cfg.kda_gate_rank

    def conv(t, name):
        return layers.short_conv(t, la["short_conv_kernel_size"],
                                 name=prefix + name + "_conv")

    q = conv(_linear(a, width, prefix + "_q"), "_q")
    k = conv(_linear(a, width, prefix + "_k"), "_k")
    v = conv(_linear(a, width, prefix + "_v"), "_v")
    a_log, dt_bias = kda_gate_start(h, dh)
    g = layers.kda_gate(
        _linear(_linear(a, rank, prefix + "_f_a"), width,
                prefix + "_f_b"),
        _gate_parameter(prefix + "_gate.A_log", a_log),
        _gate_parameter(prefix + "_gate.dt_bias", dt_bias))
    beta = layers.sigmoid(_linear(a, h, prefix + "_b"))
    o = layers.kda_attention(q, k, v, g, beta, scale=dh ** -0.5)
    gate = _linear(_linear(a, rank, prefix + "_g_a"), width,
                   prefix + "_g_b")
    o = layers.gated_rms_norm(o, gate, dh, epsilon=cfg.rms_norm_eps,
                              name=prefix + "_o_norm")
    return _linear(o, cfg.hidden_size, prefix + "_out")


def kimi_linear_lm(cfg, is_test=False):
    """Causal-LM training graph. Feeds: ``ids``, ``labels`` [b, s]
    int64; ``mask`` [b, s] float32 (1 where the position's loss
    counts). Returns ``(loss, token_num)``: the mean cross-entropy
    over the masked positions first."""
    del is_test                      # no dropout anywhere in the block
    s, d = cfg.seq_len, cfg.hidden_size
    ids = layers.data("ids", shape=[s], dtype="int64")
    labels = layers.data("labels", shape=[s], dtype="int64")
    mask = layers.data("mask", shape=[s], dtype="float32")

    with name_scope("embedding"):
        h = layers.embedding(ids, size=(cfg.vocab_size, d),
                             param_attr=ParamAttr(name="embed_tokens"))

    full = set(cfg.linear_attn_config["full_attn_layers"])
    for i in range(cfg.num_hidden_layers):
        p = "layer%d" % i
        with name_scope("residual_norm"):
            a = _norm(h, cfg, p + "_input_norm")
        mixed = latent_attention(a, cfg, p + "_mla") if i + 1 in full \
            else _kda(a, cfg, p + "_kda")
        with name_scope("residual_norm"):
            h = layers.elementwise_add(h, mixed)
            m = _norm(h, cfg, p + "_mlp_norm")
        if i < cfg.first_k_dense_replace:
            with name_scope("ffn"):
                f = _gated_mlp(m, cfg.intermediate_size, cfg, p + "_mlp")
        else:
            f = _moe(m, cfg, p)
        with name_scope("residual_norm"):
            h = layers.elementwise_add(h, f)

    with name_scope("residual_norm"):
        h = _norm(h, cfg, "final_norm")
    with name_scope("vocab_head"):
        cost = layers.fused_linear_cross_entropy(
            h, layers.unsqueeze(labels, [2]), cfg.vocab_size,
            name="lm_head")
    with name_scope("loss"):
        cost = layers.elementwise_mul(layers.squeeze(cost, [2]), mask)
        token_num = layers.reduce_sum(mask)
        loss = layers.elementwise_div(layers.reduce_sum(cost), token_num)
    return loss, token_num
