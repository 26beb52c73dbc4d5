"""Plain float32 reference of transformer-base training (Vaswani et
al. 2017, Table 3 "base"): post-LN encoder-decoder, sinusoidal
positions, ReLU feed-forward, label-smoothed cross-entropy averaged
over non-pad target tokens.

Departures from the paper, the program's own and stated in
``configs/transformer_base.json``: no projection biases in attention;
the three embedding / pre-softmax matrices are separate unless
``weight_sharing``; label smoothing in its closed form
``lse - (1-eps) logit[y] - eps/V sum(logits)`` (the cross-entropy
against the smoothed target, without the constant entropy term).
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C


def param_spec(cfg):
    d, f, L = cfg["d_model"], cfg["d_ffn"], cfg["n_layer"]
    spec = []

    def att(p):
        for t in ("q", "k", "v", "out"):
            spec.append(("%s_%s.w_0" % (p, t), (d, d), "xavier"))
        ln(p)

    def ln(p):
        spec.append((p + "_ln.w_0", (d,), "ones"))
        spec.append((p + "_ln.b_0", (d,), "zeros"))

    def ffn(p):
        spec.append((p + "_fc1.w_0", (d, f), "xavier"))
        spec.append((p + "_fc1.b_0", (f,), "zeros"))
        spec.append((p + "_fc2.w_0", (f, d), "xavier"))
        spec.append((p + "_fc2.b_0", (d,), "zeros"))
        ln(p)

    spec.append(("src_word_emb", (cfg["src_vocab"], d), "xavier"))
    for i in range(L):
        att("enc%d_att" % i)
        ffn("enc%d_ffn" % i)
    if not cfg.get("weight_sharing"):
        spec.append(("tgt_word_emb", (cfg["tgt_vocab"], d), "xavier"))
    for i in range(L):
        att("dec%d_self" % i)
        att("dec%d_cross" % i)
        ffn("dec%d_ffn" % i)
    spec.append(("proj.w_0", (d, cfg["tgt_vocab"]), "xavier"))
    return spec


def _positions(s, d):
    pos = np.arange(s)[:, None].astype(np.float64)
    dim = np.arange(d // 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, 2.0 * dim / d)
    table = np.zeros((s, d), np.float32)
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle)
    return jnp.asarray(table)


def normalizers(batch):
    return {"tokens": jnp.sum(batch["tgt_mask"])}


def block_loss(params, rows, norm, key, cfg, mode):
    """Share of the batch loss that these rows carry: the sum of their
    non-pad target tokens' losses over the batch's token count."""
    p, d, h = params, cfg["d_model"], cfg["n_head"]
    rate = cfg["dropout"]
    keys = iter(jax.random.split(key, 64 * cfg["n_layer"] + 8))
    s = rows["src_ids"].shape[1]
    pos = _positions(s, d)

    def mha(q_in, kv_in, bias, pre):
        q = C.linear(q_in, p[pre + "_q.w_0"], None, mode)
        k = C.linear(kv_in, p[pre + "_k.w_0"], None, mode)
        v = C.linear(kv_in, p[pre + "_v.w_0"], None, mode)
        ctx = C.attention(q, k, v, bias, h, rate, next(keys), mode)
        return C.linear(ctx, p[pre + "_out.w_0"], None, mode)

    def post(x, residual, pre):
        return C.layer_norm(C.dropout(x, rate, next(keys)) + residual,
                            p[pre + "_ln.w_0"], p[pre + "_ln.b_0"])

    def ffn(x, pre):
        hid = jax.nn.relu(C.linear(x, p[pre + "_fc1.w_0"],
                                   p[pre + "_fc1.b_0"], mode))
        return C.linear(hid, p[pre + "_fc2.w_0"], p[pre + "_fc2.b_0"],
                        mode)

    def embed(ids, table):
        return C.dropout(table[ids] * d ** 0.5 + pos, rate, next(keys))

    src_bias = ((rows["src_mask"] - 1.0) * 1e9)[:, None, None, :]
    tgt_bias = ((rows["tgt_mask"] - 1.0) * 1e9)[:, None, None, :] \
        + jnp.triu(jnp.full((s, s), -1e9, jnp.float32), 1)[None, None]

    x = embed(rows["src_ids"], p["src_word_emb"])
    for i in range(cfg["n_layer"]):
        pre = "enc%d" % i
        x = post(mha(x, x, src_bias, pre + "_att"), x, pre + "_att")
        x = post(ffn(x, pre + "_ffn"), x, pre + "_ffn")
    enc = x
    tgt_table = p["src_word_emb"] if cfg.get("weight_sharing") \
        else p["tgt_word_emb"]
    x = embed(rows["tgt_ids"], tgt_table)
    for i in range(cfg["n_layer"]):
        pre = "dec%d" % i
        x = post(mha(x, x, tgt_bias, pre + "_self"), x, pre + "_self")
        x = post(mha(x, enc, src_bias, pre + "_cross"), x,
                 pre + "_cross")
        x = post(ffn(x, pre + "_ffn"), x, pre + "_ffn")

    logits = C.linear(x, p["proj.w_0"], None, mode)
    eps, vocab = cfg["label_smooth_eps"], cfg["tgt_vocab"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, rows["lbl_ids"][..., None], axis=-1)[..., 0]
    tok = lse - (1.0 - eps) * picked \
        - (eps / vocab) * jnp.sum(logits, axis=-1)
    return jnp.sum(tok * rows["tgt_mask"]) / norm["tokens"]
