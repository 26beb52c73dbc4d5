"""Operations and bytes the DeepSeek-V3 block needs, from shapes alone
(``configs/kanana2_ep8.json`` names ``step_flops``; the
``dsv3_kernels_roofline`` metric reads the kernel costs).

``step_flops`` counts what one training step REQUIRES for the real
tokens of its batch, as ``flops.py``, ``flops_afmoe.py`` and
``flops_kimi_linear.py`` do: linear terms by real tokens; latent
attention, in every layer, by the causal pairs each row may read, at
``qk_nope_head_dim + qk_rope_head_dim`` lanes for the scores and
``v_head_dim`` for the values; the routed experts at the uniform share
of the assignments; nothing for the rotation (three multiply-adds a
rotary lane: a ten-thousandth of the step), nothing for recomputation;
backward twice forward.

The kernels' costs are the ones the two other sparse configurations
count by, imported and not copied: ``flops_kimi_linear.mla_flash_cost``
(one latent-attention site's forward + backward calls) and
``flops_afmoe.gmm_cost`` (one expert layer's grouped products).
"""

from benchmark.flops_afmoe import causal_pairs, gmm_cost
from benchmark.flops_kimi_linear import mla_flash_cost


def step_flops(args, lengths, predictions=0):
    d = args["hidden_size"]
    h = args["num_attention_heads"]
    dn, dr, dv = (args["qk_nope_head_dim"], args["qk_rope_head_dim"],
                  args["v_head_dim"])
    r, f = args["kv_lora_rank"], args["moe_intermediate_size"]
    tokens = sum(lengths)
    # multiply-adds a token, by layer kind
    mla = (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
           + h * dv * d)
    dense = 3 * d * args["intermediate_size"]
    held_share = args["num_experts_per_tok"] * args["n_routed_experts"] \
        / args["num_experts_published"]
    expert = d * args["num_experts_published"] \
        + 3 * d * f * args["n_shared_experts"] + held_share * 3 * d * f
    n_layers, n_dense = (args["num_hidden_layers"],
                         args["first_k_dense_replace"])
    macs = tokens * (n_layers * mla + n_dense * dense
                     + (n_layers - n_dense) * expert
                     + d * args["vocab_size"])
    # QK^T over dn + dr lanes and PV over dv, a pair and head
    macs += n_layers * h * (dn + dr + dv) \
        * sum(causal_pairs(n) for n in lengths)
    return 3 * 2 * macs


def kernels_least_seconds(args, lengths, held_rows_per_layer, peak):
    """Least time the chip could take for one step's Mosaic calls: the
    latent-attention site of every layer and the grouped products of
    every expert layer at the rows the step's counters counted, each
    the larger of operations over the peak and bytes over the
    bandwidth."""
    def least(cost):
        return max(cost[0] / peak["bf16_flops"],
                   cost[1] / peak["hbm_bytes_per_s"])

    n_layers = args["num_hidden_layers"]
    attention = n_layers * least(mla_flash_cost(
        lengths, args["num_attention_heads"],
        args["qk_nope_head_dim"] + args["qk_rope_head_dim"],
        args["v_head_dim"]))
    experts = (n_layers - args["first_k_dense_replace"]) * least(gmm_cost(
        held_rows_per_layer, args["n_routed_experts"],
        args["hidden_size"], args["moe_intermediate_size"]))
    return attention + experts
