"""Latent attention (MLA, DeepSeek-V2/V3's; arXiv:2405.04434,
arXiv:2412.19437) as the models here train it, un-absorbed: the ONE
mixer ``models/kimi_linear.py`` (every fourth layer, no positions) and
``models/deepseek_v3.py`` (every layer, a rotary part beside the plain
lanes) both build.

  q = a W_q                      [h, dn + dr]  (no query compression)
  [c | k_r] = a W_kva            kv_lora_rank + dr
  [k_n | v] = rms(c) W_kvb       [h, dn + dv]
  k = [k_n | k_r], k_r ONE vector a token, shared by every head
  y = concat_heads(softmax(q k^T (dn + dr)^-0.5, causal) v) W_o

**Positions** are a property of the configuration. ``cfg.mla_use_nope``
true: none, the ``dr`` shared lanes are plain lanes. False: lanes
``[dn, dn + dr)`` of every query head and the shared ``k_r`` are turned
by ``layers.rotary_embedding`` (``cfg.rope_theta``; ``cfg.
rope_interleave`` pairs lanes (2i, 2i + 1), as the public DeepSeek-V3
code reads its checkpoints), ``k_r`` BEFORE it is spread over the
heads: one [b, 1, s, dr] rotation, not h.

The configuration gives ``seq_len``, ``num_attention_heads``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``kv_lora_rank``, ``hidden_size``, ``rms_norm_eps`` and
``mla_use_nope``. Layer kind (``name_scope``): ``mla``.
"""

from __future__ import annotations

from .. import layers
from ..framework import name_scope
from .afmoe import _linear, _norm

__all__ = ["latent_attention"]


@name_scope("mla")
def latent_attention(a, cfg, prefix):
    s, h = cfg.seq_len, cfg.num_attention_heads
    dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                  cfg.v_head_dim)

    def heads(t, width):              # [b, s, h * width] -> [b, h, s, width]
        return layers.transpose(layers.reshape(t, (-1, s, h, width)),
                                (0, 2, 1, 3))

    q = heads(_linear(a, h * (dn + dr), prefix + "_q"), dn + dr)
    latent, k_shared = layers.split(
        _linear(a, cfg.kv_lora_rank + dr, prefix + "_kv_a"),
        [cfg.kv_lora_rank, dr], dim=2)
    kv = heads(_linear(_norm(latent, cfg, prefix + "_kv_a_norm"),
                       h * (dn + dv), prefix + "_kv_b"), dn + dv)
    k_own, v = layers.split(kv, [dn, dv], dim=3)
    k_shared = layers.reshape(k_shared, (-1, 1, s, dr))
    if not cfg.mla_use_nope:
        q = layers.rotary_embedding(
            q, theta=cfg.rope_theta, start=dn, width=dr,
            interleaved=cfg.rope_interleave)
        k_shared = layers.rotary_embedding(
            k_shared, theta=cfg.rope_theta,
            interleaved=cfg.rope_interleave)
    # one vector a token for every head
    k = layers.concat([k_own, layers.expand(k_shared, [1, h, 1, 1])],
                      axis=3)
    o = layers.scaled_dot_product_attention(
        q, k, v, scale=(dn + dr) ** -0.5, causal=True)
    o = layers.reshape(layers.transpose(o, (0, 2, 1, 3)),
                       (-1, s, h * dv))
    return _linear(o, cfg.hidden_size, prefix + "_out")
