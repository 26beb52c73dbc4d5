"""Operations and bytes the Kimi Linear block needs, from shapes alone
(``configs/kimi_linear_ep32.json`` names ``step_flops``; the
``kimi_kernels_roofline`` metric reads the kernel costs).

``step_flops`` counts what one training step REQUIRES for the real
tokens of its batch, as ``flops.py`` and ``flops_afmoe.py`` do: linear
terms by real tokens; latent attention by the causal pairs each row may
read, at 192 lanes for the scores and 128 for the values; the KDA core
by the RECURRENCE's three dk x dv contractions a token and head (k^T S,
the rank-one update, q^T S), not by a chunked form's extra products;
the routed experts at the uniform share of the assignments; nothing
for recomputation; backward twice forward.

``mla_flash_cost`` counts one site's forward + backward by what the
algorithm needs, whatever implements it; the grouped products' cost is
``flops_afmoe.gmm_cost``. The KDA core is XLA's own fusions, no Mosaic
call, so it has no kernel cost here yet.
"""

from benchmark.flops_afmoe import causal_pairs, gmm_cost


def _kinds(args):
    full = set(args["linear_attn_config"]["full_attn_layers"])
    return ["mla" if i + 1 in full else "kda"
            for i in range(args["num_hidden_layers"])]


def step_flops(args, lengths, predictions=0):
    d = args["hidden_size"]
    la = args["linear_attn_config"]
    hk, dk, taps = (la["num_heads"], la["head_dim"],
                    la["short_conv_kernel_size"])
    wk, rank = hk * dk, args["kda_gate_rank"]
    h = args["num_attention_heads"]
    dn, dr, dv = (args["qk_nope_head_dim"], args["qk_rope_head_dim"],
                  args["v_head_dim"])
    r, f = args["kv_lora_rank"], args["moe_intermediate_size"]
    tokens = sum(lengths)
    # multiply-adds a token, by layer kind
    kda = (4 * d * wk                       # q, k, v, out
           + 2 * (d * rank + rank * wk)     # the decay's and gate's pairs
           + d * hk + 3 * wk * taps         # beta, the convolutions
           + 3 * hk * dk * dk)              # the recurrence
    mla = (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
           + h * dv * d)
    dense = 3 * d * args["intermediate_size"]
    held_share = args["num_experts_per_token"] * args["num_experts"] \
        / args["num_experts_published"]
    expert = d * args["num_experts_published"] \
        + 3 * d * f * args["num_shared_experts"] + held_share * 3 * d * f
    kinds = _kinds(args)
    n_dense = args["first_k_dense_replace"]
    macs = tokens * (kinds.count("kda") * kda + kinds.count("mla") * mla
                     + n_dense * dense
                     + (len(kinds) - n_dense) * expert
                     + d * args["vocab_size"])
    # QK^T over 192 lanes and PV over 128, a pair and head
    macs += kinds.count("mla") * h * (dn + dr + dv) \
        * sum(causal_pairs(n) for n in lengths)
    return 3 * 2 * macs


def mla_flash_cost(lengths, n_head, d_qk, d_v):
    """(flops, bytes) of one latent-attention site's forward + backward
    calls over rows of ``lengths``. Forward QK^T (d_qk) and PV (d_v);
    backward one recomputed QK^T, dQ and dK (d_qk each), dV and dP (d_v
    each): 2 x pairs x (4 d_qk + 3 d_v) a head. Bytes in bf16: q and k
    read forward and backward and their gradients written (3 d_qk
    each), v likewise, o written forward and read backward beside its
    gradient (3 d_v each)."""
    pairs = sum(causal_pairs(n) for n in lengths)
    flops = 2 * pairs * (4 * d_qk + 3 * d_v) * n_head
    return flops, 2 * sum(lengths) * n_head * 6 * (d_qk + d_v)


def kernels_least_seconds(args, lengths, held_rows_per_layer, peak):
    """Least time the chip could take for one step's Mosaic calls: the
    latent-attention sites and the grouped products layer by layer at
    the rows the step's counters counted."""
    def least(cost):
        return max(cost[0] / peak["bf16_flops"],
                   cost[1] / peak["hbm_bytes_per_s"])

    total = _kinds(args).count("mla") * least(mla_flash_cost(
        lengths, args["num_attention_heads"],
        args["qk_nope_head_dim"] + args["qk_rope_head_dim"],
        args["v_head_dim"]))
    n_moe = args["num_hidden_layers"] - args["first_k_dense_replace"]
    total += n_moe * least(gmm_cost(
        held_rows_per_layer, args["num_experts"], args["hidden_size"],
        args["moe_intermediate_size"]))
    return total
