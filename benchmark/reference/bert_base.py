"""Plain float32 reference of BERT-base pretraining (Devlin et al.
2018; google-research/bert ``modeling.py`` / ``run_pretraining.py``):
post-LN encoder, learned positions, tanh-approximated GELU, masked-LM
loss over the predicted positions (weighted mean) plus next-sentence
loss (batch mean).

Departure, the program's own and stated in ``configs/bert_base.json``:
the masked-LM output matrix is a parameter of its own, not the
transposed word embedding.
"""

import jax
import jax.numpy as jnp

from . import common as C


def param_spec(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    # every matrix as modeling.py's create_initializer draws it
    draw = "tnormal%g" % cfg["initializer_range"]
    spec = [("word_embedding", (cfg["vocab_size"], d), draw),
            ("sent_embedding", (cfg["type_vocab_size"], d), draw),
            ("pos_embedding", (cfg["max_position_embeddings"], d),
             draw)]

    def ln(p):
        spec.append((p + ".w_0", (d,), "ones"))
        spec.append((p + ".b_0", (d,), "zeros"))

    def fc(p, n_in, n_out):
        spec.append((p + ".w_0", (n_in, n_out), draw))
        spec.append((p + ".b_0", (n_out,), "zeros"))

    ln("emb_ln")
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d" % i
        for t in ("q", "k", "v", "out"):
            fc("%s_att_%s" % (p, t), d, d)
        ln(p + "_att_ln")
        fc(p + "_ffn_fc1", d, f)
        fc(p + "_ffn_fc2", f, d)
        ln(p + "_ffn_ln")
    fc("pooler", d, d)
    fc("mlm_trans", d, d)
    ln("mlm_ln")
    fc("mlm_out", d, cfg["vocab_size"])
    fc("nsp_out", d, 2)
    return spec


def normalizers(batch):
    return {"predictions": jnp.sum(batch["mask_weight"]),
            "rows": jnp.float32(batch["src_ids"].shape[0])}


def block_loss(params, rows, norm, key, cfg, mode):
    """These rows' share of ``mlm_loss + nsp_loss``. ``mask_pos`` holds
    positions in the row (the traffic generator's own layout); slots
    with weight 0 point at position 0 and add nothing."""
    p, h = params, cfg["num_attention_heads"]
    rate, att_rate = cfg["hidden_dropout_prob"], \
        cfg["attention_probs_dropout_prob"]
    keys = iter(jax.random.split(key, 8 * cfg["num_hidden_layers"] + 8))
    s = rows["src_ids"].shape[1]

    def fc(x, pre):
        return C.linear(x, p[pre + ".w_0"], p[pre + ".b_0"], mode)

    def ln(x, pre):
        return C.layer_norm(x, p[pre + ".w_0"], p[pre + ".b_0"])

    x = p["word_embedding"][rows["src_ids"]] \
        + p["sent_embedding"][rows["sent_ids"]] \
        + p["pos_embedding"][:s]
    x = C.dropout(ln(x, "emb_ln"), rate, next(keys))
    bias = ((rows["input_mask"] - 1.0) * 1e9)[:, None, None, :]
    for i in range(cfg["num_hidden_layers"]):
        pre = "layer%d" % i
        ctx = C.attention(fc(x, pre + "_att_q"), fc(x, pre + "_att_k"),
                          fc(x, pre + "_att_v"), bias, h, att_rate,
                          next(keys), mode)
        att = fc(ctx, pre + "_att_out")
        x = ln(C.dropout(att, rate, next(keys)) + x, pre + "_att_ln")
        ff = fc(jax.nn.gelu(fc(x, pre + "_ffn_fc1"), approximate=True),
                pre + "_ffn_fc2")
        x = ln(C.dropout(ff, rate, next(keys)) + x, pre + "_ffn_ln")

    pooled = jnp.tanh(fc(x[:, 0], "pooler"))
    picked = jnp.take_along_axis(
        x, rows["mask_pos_in_row"][..., None], axis=1)      # [b, P, d]
    trans = ln(jax.nn.gelu(fc(picked, "mlm_trans"), approximate=True),
               "mlm_ln")
    logits = fc(trans, "mlm_out")
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, rows["mask_label"][..., None], axis=-1)[..., 0]
    mlm = jnp.sum((lse - gold) * rows["mask_weight"]) \
        / norm["predictions"]
    nsp_logits = fc(pooled, "nsp_out")
    nsp = jax.scipy.special.logsumexp(nsp_logits, axis=-1) \
        - jnp.take_along_axis(nsp_logits, rows["nsp_label"], axis=-1)[
            ..., 0]
    return mlm + jnp.sum(nsp) / norm["rows"]
