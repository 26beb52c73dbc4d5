"""Operations and bytes the AFMoE block needs, from shapes alone
(``configs/trinity_mini_ep16.json`` names ``step_flops``; the
``lm_kernels_roofline`` metric reads the two kernel costs).

``step_flops`` counts what one training step REQUIRES for the real
tokens of its batch, as ``flops.py`` does: linear terms by real tokens,
attention by the pairs (query, key) each row may read -- causal, and
within the window in sliding layers --, the routed experts at the
UNIFORM share of the assignments (tokens x top_k x held / published:
what routing costs when the router balances, whatever it did in the
step), nothing for recomputation, backward twice forward.

``blocked_flash_cost`` and ``gmm_cost`` count one step's calls of a
kernel family by what the algorithm needs (the same work whatever
implements it): attention by its exact pairs, not by the tiles a kernel
touches, the grouped products by the rows COUNTED, not the rows padded
to a tile.
"""


def causal_pairs(length, window=0):
    """(query, key) pairs of one row of ``length`` positions: key <=
    query, and query - key < window where there is one."""
    if not window or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def _layer_windows(args):
    return [args["sliding_window"] if t == "sliding_attention" else 0
            for t in args["layer_types"]]


def step_flops(args, lengths, predictions=0):
    d, dh = args["hidden_size"], args["head_dim"]
    h, hkv = args["num_attention_heads"], args["num_key_value_heads"]
    f = args["moe_intermediate_size"]
    tokens = sum(lengths)
    # multiply-adds a token, by layer kind
    attn = d * h * dh * 3 + d * hkv * dh * 2     # q, gate, out; k, v
    dense = 3 * d * args["intermediate_size"]
    held_share = args["num_experts_per_tok"] * args["num_experts"] \
        / args["num_experts_published"]
    expert = d * args["num_experts_published"] \
        + 3 * d * f * args["num_shared_experts"] \
        + held_share * 3 * d * f
    n_dense = args["num_dense_layers"]
    n_moe = args["num_hidden_layers"] - n_dense
    macs = tokens * (args["num_hidden_layers"] * attn + n_dense * dense
                     + n_moe * expert + d * args["vocab_size"])
    # QK^T and PV: two multiply-adds of head_dim a pair and head
    for window in _layer_windows(args):
        macs += 2 * h * dh * sum(causal_pairs(n, window)
                                 for n in lengths)
    return 3 * 2 * macs


def blocked_flash_cost(lengths, n_head, n_kv_head, dh, window):
    """(flops, bytes) of one attention site's forward + backward calls
    over rows of ``lengths``. Forward QK^T and PV; backward one
    recomputed QK^T, dV, dP, dQ, dK: 7 contractions of 2 x pairs x dh a
    head (the blocked kernels recompute QK^T and dP once more, in the
    second backward kernel: that is theirs, not the algorithm's).
    Bytes in bf16: q, o, do, dq at the query heads and k, v, dk, dv at
    the key heads, each once forward and once backward where both
    passes touch it."""
    pairs = sum(causal_pairs(n, window) for n in lengths)
    flops = 7 * 2 * pairs * dh * n_head
    # forward q, o | k, v; backward q, o, do, dq | k, v, dk, dv
    bytes_ = 2 * dh * sum(lengths) * 6 * (n_head + n_kv_head)
    return flops, bytes_


def gmm_cost(rows, n_groups, d, f):
    """(flops, bytes) of one expert layer's grouped products, forward
    and backward, over ``rows`` real rows (not padded): gate, up and
    down, each with the gradient of its rows and of its matrices: 9
    products of 2 x rows x d x f. Bytes in bf16: each matrix read
    forward, read again for the rows' gradient and its own gradient
    written; each product's operand and result rows once forward, its
    operand, cotangent and the rows' gradient once backward."""
    flops = 9 * 2 * rows * d * f
    weights = 3 * 3 * n_groups * d * f
    acts = 0
    for k, n in ((d, f), (d, f), (f, d)):
        acts += rows * ((k + n) + (2 * k + n))
    return flops, 2 * (weights + acts)


def kernels_least_seconds(args, lengths, held_rows_per_layer, peak):
    """Least time the chip could take for one step's Mosaic calls: each
    family's larger of operations over the peak and bytes over the
    bandwidth, summed (attention site by site, the grouped products
    layer by layer at the rows the step's counters counted)."""
    def least(cost):
        return max(cost[0] / peak["bf16_flops"],
                   cost[1] / peak["hbm_bytes_per_s"])

    total = sum(least(blocked_flash_cost(
        lengths, args["num_attention_heads"],
        args["num_key_value_heads"], args["head_dim"], w))
        for w in _layer_windows(args))
    n_moe = args["num_hidden_layers"] - args["num_dense_layers"]
    return total + n_moe * least(gmm_cost(
        held_rows_per_layer, args["num_experts"], args["hidden_size"],
        args["moe_intermediate_size"]))
