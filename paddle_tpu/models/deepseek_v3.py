"""DeepSeek-V3's decoder block (``model_type`` ``deepseek_v3``;
arXiv:2412.19437) as a causal-LM training graph, at whatever sizes a
public ``config.json`` of that type gives (Kakao's Kanana-2-30B-A3B is
the one the benchmark runs).

Pre-norm blocks ``h + mixer(norm(h))``, ``h + ffn(norm(h))``. The
mixer of EVERY layer is latent attention with a decoupled rotary part
(``models/mla.py``, layer kind ``mla``): of a head's ``qk_nope_head_dim
+ qk_rope_head_dim`` query lanes the last ``qk_rope_head_dim`` are
turned, and so is the one ``qk_rope_head_dim``-wide key vector a token
that all heads share (``rope_theta``; ``rope_interleave``: lanes
(2i, 2i + 1) are a pair). The config's ``head_dim`` is that rotary
width and nothing else. After the leading ``first_k_dense_replace``
dense layers the FFN is a mixture of experts: a sigmoid router with a
selection-bias buffer (``topk_method`` ``noaux_tc`` over one group),
its top ``num_experts_per_tok`` renormalised and scaled, routed experts
plus ONE gated MLP of ``n_shared_experts`` times their width, as
``models/afmoe.py`` has it (the same two ops).

**One chip's share**, as in ``afmoe``: ``n_routed_experts`` is how
many routed experts THIS program holds (``first_held_expert`` ..) of
the ``num_experts_published`` the router scores; what the others would
add is left out.

Layer kinds (``name_scope``): ``embedding``, ``mla``, ``ffn``,
``router``, ``experts``, ``shared_expert``, ``residual_norm``,
``vocab_head``, ``loss``.
"""

from __future__ import annotations

from .. import layers
from ..framework import name_scope
from ..param_attr import ParamAttr
from .afmoe import _gated_mlp, _moe, _norm
from .mla import latent_attention

__all__ = ["DeepseekV3Config", "deepseek_v3_lm"]


class DeepseekV3Config:
    """Keys follow the public ``config.json``; ``load_balance_coeff``
    (the selection bias's step) has no key there; ``seq_len``,
    ``num_experts_published`` / ``first_held_expert`` and
    ``moe_row_capacity`` are this framework's, as ``AfmoeConfig`` has
    them."""

    mla_use_nope = False        # models/mla.py: the rotary part is on

    def __init__(self, vocab_size=128256, hidden_size=2048,
                 num_hidden_layers=48, first_k_dense_replace=1,
                 num_attention_heads=32, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, q_lora_rank=None,
                 intermediate_size=6144, moe_intermediate_size=768,
                 n_routed_experts=128, num_experts_published=None,
                 first_held_expert=0, n_shared_experts=2,
                 num_experts_per_tok=6, norm_topk_prob=True,
                 routed_scaling_factor=2.448, scoring_func="sigmoid",
                 topk_method="noaux_tc", n_group=1, topk_group=1,
                 rope_theta=1000000.0, rope_interleave=True,
                 rope_scaling=None, rms_norm_eps=1e-6,
                 load_balance_coeff=0.001, moe_row_capacity=None,
                 seq_len=8192):
        if q_lora_rank is not None:
            raise ValueError("q_lora_rank %r: the latent attention "
                             "here has no query compression"
                             % (q_lora_rank,))
        if rope_scaling is not None:
            raise ValueError("rope_scaling %r: the rotary part here is "
                             "unscaled" % (rope_scaling,))
        if n_group != 1 or topk_group != 1:
            raise ValueError("n_group %r, topk_group %r: the router "
                             "here is over one group of experts"
                             % (n_group, topk_group))
        if scoring_func != "sigmoid" or topk_method != "noaux_tc":
            raise ValueError("scoring_func %r, topk_method %r: the "
                             "router here is the sigmoid one with a "
                             "selection bias (noaux_tc)"
                             % (scoring_func, topk_method))
        published = num_experts_published or n_routed_experts
        if first_held_expert + n_routed_experts > published:
            raise ValueError("experts %d..%d of %d" % (
                first_held_expert,
                first_held_expert + n_routed_experts - 1, published))
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_published = published
        self.first_held_expert = first_held_expert
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.rope_theta = rope_theta
        self.rope_interleave = rope_interleave
        self.rms_norm_eps = rms_norm_eps
        self.load_balance_coeff = load_balance_coeff
        self.moe_row_capacity = moe_row_capacity
        self.seq_len = seq_len

    # the expert layer's keys under the names ``models/afmoe.py``'s
    # ``_moe`` reads them by
    num_experts = property(lambda self: self.n_routed_experts)
    num_shared_experts = property(lambda self: self.n_shared_experts)
    route_scale = property(lambda self: self.routed_scaling_factor)
    route_norm = property(lambda self: self.norm_topk_prob)


def deepseek_v3_lm(cfg, is_test=False):
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

    for i in range(cfg.num_hidden_layers):
        p = "layer%d" % i
        with name_scope("residual_norm"):
            a = _norm(h, cfg, p + "_input_norm")
        mixed = latent_attention(a, cfg, p + "_mla")
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
