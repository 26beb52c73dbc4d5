"""AFMoE: the decoder block of Arcee's Trinity family (``model_type``
``afmoe``; public ``config.json`` of arcee-ai/Trinity-Mini, modeling
code ``modeling_afmoe.py``), as a causal-LM training graph.

What the block has that no other model here has: RMSNorm before AND
after each sub-layer (``h + norm(f(norm(h)))``), grouped-query
attention with an RMSNorm on every q and k head, rotary positions in
the sliding-window layers and none in the full ones, a sigmoid output
gate on the attention, gated-SiLU MLPs, and after the leading dense
layers a mixture of experts: a sigmoid top-k router with a
load-balancing bias buffer, routed experts plus a shared one.

**One chip's share.** ``num_experts`` is how many routed experts THIS
program holds (``first_held_expert`` .. +``num_experts`` - 1) of the
``num_experts_published`` the router scores; the layer computes its
own experts' part (parallel/moe.py ``held_experts_ffn``) and what the
others would add is left out. With ``num_experts ==
num_experts_published`` it is the whole layer.

Every op is built under a ``name_scope`` naming its layer kind:
``embedding``, ``attention``, ``ffn`` (the dense layers' MLP),
``router``, ``experts``, ``shared_expert``, ``residual_norm``,
``vocab_head``, ``loss`` (profiler.scope_table charges a device trace
to them).
"""

from __future__ import annotations

from .. import layers
from ..framework import name_scope
from ..param_attr import ParamAttr

__all__ = ["AfmoeConfig", "afmoe_lm"]


class AfmoeConfig:
    """Keys follow the public ``config.json``; ``seq_len`` (the graph
    is static), ``num_experts_published`` / ``first_held_expert`` (the
    share) and ``moe_row_capacity`` (rows of the held experts' buffer,
    None = every assignment) are this framework's."""

    def __init__(self, vocab_size=200192, hidden_size=2048,
                 num_hidden_layers=32, num_dense_layers=2,
                 layer_types=None, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128,
                 intermediate_size=6144, moe_intermediate_size=1024,
                 num_experts=128, num_experts_published=None,
                 first_held_expert=0, num_experts_per_tok=8,
                 num_shared_experts=1, route_scale=2.826,
                 route_norm=True, score_func="sigmoid",
                 load_balance_coeff=0.001, sliding_window=2048,
                 rope_theta=10000.0, rms_norm_eps=1e-5,
                 mup_enabled=True, moe_row_capacity=None, seq_len=8192):
        if layer_types is None:      # the published 3:1 pattern
            layer_types = ["full_attention" if (i + 1) % 4 == 0
                           else "sliding_attention"
                           for i in range(num_hidden_layers)]
        if len(layer_types) != num_hidden_layers:
            raise ValueError("%d layer_types for %d layers"
                             % (len(layer_types), num_hidden_layers))
        if num_attention_heads % num_key_value_heads:
            raise ValueError("%d heads over %d kv heads"
                             % (num_attention_heads, num_key_value_heads))
        if score_func != "sigmoid":
            raise ValueError("score_func %r: the router here is the "
                             "sigmoid one" % (score_func,))
        published = num_experts_published or num_experts
        if first_held_expert + num_experts > published:
            raise ValueError("experts %d..%d of %d" % (
                first_held_expert, first_held_expert + num_experts - 1,
                published))
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_dense_layers = num_dense_layers
        self.layer_types = list(layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_published = published
        self.first_held_expert = first_held_expert
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.route_scale = route_scale
        self.route_norm = route_norm
        self.score_func = score_func
        self.load_balance_coeff = load_balance_coeff
        self.sliding_window = sliding_window
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.mup_enabled = mup_enabled
        self.moe_row_capacity = moe_row_capacity
        self.seq_len = seq_len


def _linear(x, size, name):
    return layers.fc(x, size, num_flatten_dims=len(x.shape) - 1,
                     bias_attr=False, name=name)


def _norm(x, cfg, name):
    return layers.rms_norm(x, epsilon=cfg.rms_norm_eps, name=name)


@name_scope("attention")
def _attention(a, cfg, sliding, prefix):
    s = cfg.seq_len
    h, hkv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)

    def heads(t, n):
        return layers.transpose(layers.reshape(t, (-1, s, n, dh)),
                                (0, 2, 1, 3))

    q = heads(_linear(a, h * dh, prefix + "_q"), h)
    k = heads(_linear(a, hkv * dh, prefix + "_k"), hkv)
    v = heads(_linear(a, hkv * dh, prefix + "_v"), hkv)
    gate = _linear(a, h * dh, prefix + "_gate")
    q = _norm(q, cfg, prefix + "_q_norm")
    k = _norm(k, cfg, prefix + "_k_norm")
    if sliding:       # the full-attention layers carry no position
        q = layers.rotary_embedding(q, theta=cfg.rope_theta)
        k = layers.rotary_embedding(k, theta=cfg.rope_theta)
    o = layers.scaled_dot_product_attention(
        q, k, v, scale=dh ** -0.5, causal=True,
        window=cfg.sliding_window if sliding else 0)
    o = layers.reshape(layers.transpose(o, (0, 2, 1, 3)),
                       (-1, s, h * dh))
    o = layers.elementwise_mul(o, layers.sigmoid(gate))
    return _linear(o, cfg.hidden_size, prefix + "_out")


def _gated_mlp(m, width, cfg, prefix):
    act = layers.swish(_linear(m, width, prefix + "_gate"))
    up = _linear(m, width, prefix + "_up")
    return _linear(layers.elementwise_mul(act, up), cfg.hidden_size,
                   prefix + "_down")


def _routed(m, cfg, prefix):
    """What the experts held here give for ``m`` [b, s, d]: the router
    over the published width, then this share's experts."""
    d = cfg.hidden_size
    with name_scope("router"):
        flat = layers.reshape(m, (-1, d))
        idx, weight = layers.moe_sigmoid_router(
            flat, cfg.num_experts_published, cfg.num_experts_per_tok,
            route_scale=cfg.route_scale, route_norm=cfg.route_norm,
            balance_coeff=cfg.load_balance_coeff,
            first_held=cfg.first_held_expert, num_held=cfg.num_experts,
            name=prefix + "_router")
    with name_scope("experts"):
        routed = layers.moe_held_experts(
            flat, idx, weight, cfg.num_experts,
            cfg.moe_intermediate_size,
            first_held=cfg.first_held_expert,
            row_capacity=cfg.moe_row_capacity, name=prefix + "_experts")
        return layers.reshape(routed, (-1, cfg.seq_len, d))


def _moe(m, cfg, prefix):
    routed = _routed(m, cfg, prefix)
    with name_scope("shared_expert"):
        shared = _gated_mlp(
            m, cfg.moe_intermediate_size * cfg.num_shared_experts, cfg,
            prefix + "_shared")
        return layers.elementwise_add(shared, routed)


def afmoe_lm(cfg, is_test=False):
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
        if cfg.mup_enabled:
            h = layers.scale(h, scale=float(d) ** 0.5)

    for i in range(cfg.num_hidden_layers):
        p = "layer%d" % i
        sliding = cfg.layer_types[i] == "sliding_attention"
        with name_scope("residual_norm"):
            a = _norm(h, cfg, p + "_input_norm")
        att = _attention(a, cfg, sliding, p + "_att")
        with name_scope("residual_norm"):
            h = layers.elementwise_add(
                h, _norm(att, cfg, p + "_post_att_norm"))
            m = _norm(h, cfg, p + "_pre_mlp_norm")
        if i < cfg.num_dense_layers:
            with name_scope("ffn"):
                f = _gated_mlp(m, cfg.intermediate_size, cfg, p + "_mlp")
        else:
            f = _moe(m, cfg, p)
        with name_scope("residual_norm"):
            h = layers.elementwise_add(
                h, _norm(f, cfg, p + "_post_mlp_norm"))

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
