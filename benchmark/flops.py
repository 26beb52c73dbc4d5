"""Operations and bytes the algorithms need, from shapes alone.

``*_step_flops`` count what one training step REQUIRES for the real
tokens of its batch: linear terms by real tokens, attention by each
sequence's own length (causal attention by the lower triangle), BERT's
masked-LM head at the predicted positions only. Pad positions earn
nothing, recomputation earns nothing; backward is twice forward.

``bench.py``'s ``transformer_flops_per_step`` / ``bert_flops_per_step``
are the originals: they pay every padded position and run BERT's head
at all S positions. Not used here.

``flash_1k_cost`` counts one step's calls of the Pallas single-k-block
attention pair by the shapes the calls are given (dense [Sq, Sk] per
head: the kernel is handed an additive bias and cannot skip a pad or
the causal half).
"""


def transformer_step_flops(args, lengths, predictions=0):
    d, f, n_layer = args["d_model"], args["d_ffn"], args["n_layer"]
    vocab = args["tgt_vocab"]
    fwd = 0
    for length in lengths:      # source and target share a length
        enc_tok = 8 * d * d + 4 * d * f
        dec_tok = 8 * d * d + 4 * d * d + 4 * d * f
        cross_kv_tok = 4 * d * d
        enc_att = 4 * length * length * d
        dec_self = 4 * (length * (length + 1) // 2) * d
        dec_cross = 4 * length * length * d
        fwd += n_layer * (length * (enc_tok + dec_tok + cross_kv_tok)
                          + enc_att + dec_self + dec_cross)
        fwd += 2 * d * vocab * length
    return 3 * fwd


def bert_step_flops(args, lengths, predictions):
    d, f = args["hidden_size"], args["intermediate_size"]
    n_layer, vocab = args["num_hidden_layers"], args["vocab_size"]
    fwd = 0
    for length in lengths:
        fwd += n_layer * (length * (8 * d * d + 4 * d * f)
                          + 4 * length * length * d)
        fwd += 2 * d * d + 4 * d        # pooler and next-sentence head
    fwd += predictions * (2 * d * d + 2 * d * vocab)
    return 3 * fwd


def flash_1k_cost(sites, batch, n_head, sq, sk, dh):
    """(flops, bytes) of one step's forward+backward calls at ``sites``
    attention sites. Forward: QK^T and PV; backward: recomputed QK^T,
    dV, dP, dQ, dK -- 7 contractions of 2*sq*sk*dh. Bytes: q, k, v, o
    in and out in bf16: forward q, k, v, o; backward q, k, v, o,
    do in and dq, dk, dv out."""
    heads = batch * n_head
    flops = sites * heads * 7 * 2 * sq * sk * dh
    bytes_per_head = 2 * 6 * (sq + sk) * dh
    return flops, sites * heads * bytes_per_head
