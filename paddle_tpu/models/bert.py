"""BERT (BASELINE.json config 4: BERT-base data-parallel pretraining).

Reference parity target: the fluid-era LARK/ERNIE BERT implementations
built on this op set (fc/layer_norm/dropout/matmul/softmax) — written
here TPU-first from this framework's primitives:

  - static [batch, seq] shapes, pad masks as additive biases;
  - post-LN encoder (original BERT ordering);
  - MLM loss gathers masked positions with a static max_predictions
    slot count (pad + weight, no dynamic shapes under jit);
  - one XLA program per pretrain step; dp sharding via
    CompiledProgram.with_data_parallel, tp via shard_tp below;
  - every op is built under a ``name_scope`` naming its layer kind
    (embedding, attention, ffn, residual_norm, pooler, vocab_head,
    loss), which is what a device trace is charged to
    (profiler.scope_table).
"""

from __future__ import annotations

import numpy as np

from .. import layers
from ..framework import name_scope
from ..param_attr import ParamAttr

__all__ = ["BertConfig", "bert_encoder", "bert_pretrain",
           "bert_classifier", "shard_tp", "make_fake_pretrain_batch"]


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768,
                 num_hidden_layers=12, num_attention_heads=12,
                 intermediate_size=3072, max_position_embeddings=512,
                 type_vocab_size=2, hidden_dropout_prob=0.1,
                 attention_probs_dropout_prob=0.1, seq_len=128,
                 max_predictions_per_seq=20):
        if hidden_size % num_attention_heads:
            raise ValueError("hidden_size %d %% num_attention_heads %d"
                             % (hidden_size, num_attention_heads))
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.seq_len = seq_len
        self.max_predictions_per_seq = max_predictions_per_seq


def base():
    return BertConfig()


@name_scope("attention")
def _attention(x, bias, cfg, is_test, prefix):
    d, h = cfg.hidden_size, cfg.num_attention_heads
    dh = d // h
    q = layers.fc(x, d, num_flatten_dims=2, name=prefix + "_q")
    k = layers.fc(x, d, num_flatten_dims=2, name=prefix + "_k")
    v = layers.fc(x, d, num_flatten_dims=2, name=prefix + "_v")
    # fused attention (pallas flash kernel when enabled) on the
    # projections' own [b, s, h * dh] layout: no head split or merge;
    # attention dropout runs in-kernel so scores never materialize in
    # HBM
    ctx = layers.scaled_dot_product_attention(
        q, k, v, bias=bias, scale=dh ** -0.5,
        dropout_rate=cfg.attention_probs_dropout_prob, is_test=is_test,
        num_heads=h)
    return layers.fc(ctx, d, num_flatten_dims=2, name=prefix + "_out")


@name_scope("residual_norm")
def _residual_ln(x, residual, cfg, is_test, name):
    if cfg.hidden_dropout_prob and not is_test:
        x = layers.dropout(x, cfg.hidden_dropout_prob,
                           dropout_implementation="upscale_in_train")
    return layers.layer_norm(layers.elementwise_add(x, residual),
                             begin_norm_axis=2, name=name)


def bert_encoder(src_ids, sent_ids, input_mask, cfg, is_test=False):
    """Returns (sequence_output [b,s,d], pooled_output [b,d])."""
    with name_scope("embedding"):
        emb = layers.embedding(
            src_ids, size=(cfg.vocab_size, cfg.hidden_size),
            param_attr=ParamAttr(name="word_embedding"))
        sent = layers.embedding(
            sent_ids, size=(cfg.type_vocab_size, cfg.hidden_size),
            param_attr=ParamAttr(name="sent_embedding"))
        # static position ids 0..s-1 broadcast over the batch
        s = src_ids.shape[1]
        pos_ids = layers.assign(np.arange(s, dtype=np.int64))
        pos = layers.embedding(
            pos_ids,
            size=(cfg.max_position_embeddings, cfg.hidden_size),
            param_attr=ParamAttr(name="pos_embedding"))
        x = layers.elementwise_add(layers.elementwise_add(emb, sent),
                                   pos)
        x = layers.layer_norm(x, begin_norm_axis=2, name="emb_ln")
        if cfg.hidden_dropout_prob and not is_test:
            x = layers.dropout(
                x, cfg.hidden_dropout_prob,
                dropout_implementation="upscale_in_train")

    # [b, s] 1/0 -> additive bias [b, 1, 1, s]
    with name_scope("attention"):
        bias = layers.scale(input_mask, scale=1e9, bias=-1.0,
                            bias_after_scale=False)
        bias = layers.unsqueeze(layers.unsqueeze(bias, [1]), [1])

    for i in range(cfg.num_hidden_layers):
        p = "layer%d" % i
        att = _attention(x, bias, cfg, is_test, p + "_att")
        x = _residual_ln(att, x, cfg, is_test, p + "_att_ln")
        with name_scope("ffn"):
            ff = layers.fc(x, cfg.intermediate_size,
                           num_flatten_dims=2, act="gelu",
                           name=p + "_ffn_fc1")
            ff = layers.fc(ff, cfg.hidden_size, num_flatten_dims=2,
                           name=p + "_ffn_fc2")
        x = _residual_ln(ff, x, cfg, is_test, p + "_ffn_ln")

    with name_scope("pooler"):
        first_tok = layers.slice(x, axes=[1], starts=[0], ends=[1])
        first_tok = layers.squeeze(first_tok, [1])
        pooled = layers.fc(first_tok, cfg.hidden_size, act="tanh",
                           name="pooler")
    return x, pooled


def bert_pretrain(cfg, is_test=False):
    """MLM + NSP pretrain graph. Feeds:
      src_ids/sent_ids [b,s] int64; input_mask [b,s] float32;
      mask_pos [b,P] int64 (flat positions into b*s);
      mask_label [b,P] int64; mask_weight [b,P] float32;
      nsp_label [b,1] int64.
    Returns (total_loss, mlm_loss, nsp_acc)."""
    s, P = cfg.seq_len, cfg.max_predictions_per_seq
    src_ids = layers.data("src_ids", shape=[s], dtype="int64")
    sent_ids = layers.data("sent_ids", shape=[s], dtype="int64")
    input_mask = layers.data("input_mask", shape=[s], dtype="float32")
    mask_pos = layers.data("mask_pos", shape=[P], dtype="int64")
    mask_label = layers.data("mask_label", shape=[P], dtype="int64")
    mask_weight = layers.data("mask_weight", shape=[P],
                              dtype="float32")
    nsp_label = layers.data("nsp_label", shape=[1], dtype="int64")

    seq_out, pooled = bert_encoder(src_ids, sent_ids, input_mask, cfg,
                                   is_test)

    # ---- MLM head: gather masked positions from the flattened batch
    with name_scope("loss"):
        flat = layers.reshape(seq_out, (-1, cfg.hidden_size))
        gathered = layers.gather(flat, layers.reshape(mask_pos, (-1,)))
        trans = layers.fc(gathered, cfg.hidden_size, act="gelu",
                          name="mlm_trans")
        trans = layers.layer_norm(trans, name="mlm_ln")
    with name_scope("vocab_head"):
        mlm_logits = layers.fc(trans, cfg.vocab_size, name="mlm_out")
    with name_scope("loss"):
        mlm_loss_all = layers.softmax_with_cross_entropy(
            mlm_logits, layers.reshape(mask_label, (-1, 1)))
        w = layers.reshape(mask_weight, (-1, 1))
        mlm_sum = layers.reduce_sum(
            layers.elementwise_mul(mlm_loss_all, w))
        denom = layers.reduce_sum(w)
        mlm_loss = layers.elementwise_div(mlm_sum, denom)

        # ---- NSP head
        nsp_logits = layers.fc(pooled, 2, name="nsp_out")
        nsp_loss = layers.mean(layers.softmax_with_cross_entropy(
            nsp_logits, nsp_label))
        nsp_acc = layers.accuracy(layers.softmax(nsp_logits),
                                  nsp_label)

        total = layers.elementwise_add(mlm_loss, nsp_loss)
    return total, mlm_loss, nsp_acc


def bert_classifier(cfg, num_classes, is_test=False):
    """Fine-tune graph: encoder + softmax over pooled output.
    Feeds: src_ids/sent_ids/input_mask + label [b,1] int64.
    Returns (loss, accuracy, probs)."""
    s = cfg.seq_len
    src_ids = layers.data("src_ids", shape=[s], dtype="int64")
    sent_ids = layers.data("sent_ids", shape=[s], dtype="int64")
    input_mask = layers.data("input_mask", shape=[s], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    _, pooled = bert_encoder(src_ids, sent_ids, input_mask, cfg,
                             is_test)
    with name_scope("loss"):
        if cfg.hidden_dropout_prob and not is_test:
            pooled = layers.dropout(
                pooled, cfg.hidden_dropout_prob,
                dropout_implementation="upscale_in_train")
        logits = layers.fc(pooled, num_classes, name="cls_out")
        probs = layers.softmax(logits)
        loss = layers.mean(
            layers.softmax_with_cross_entropy(logits, label))
        acc = layers.accuracy(probs, label)
    return loss, acc, probs


def shard_tp(program, axis="tp"):
    """Megatron-style tp annotations: q/k/v + ffn_fc1 column-parallel,
    att_out + ffn_fc2 row-parallel, embeddings vocab-sharded, MLM output
    vocab-sharded on its output dim."""
    from ..parallel import shard
    for p in program.all_parameters():
        if len(p.shape) != 2:
            continue
        n = p.name
        if any(t in n for t in ("_q.", "_k.", "_v.", "_ffn_fc1.")):
            shard(p, None, axis)
        elif any(t in n for t in ("_att_out.", "_ffn_fc2.")):
            shard(p, axis, None)
        elif "word_embedding" in n:
            shard(p, axis, None)
        elif n.startswith("mlm_out"):
            shard(p, None, axis)
    return program


def make_fake_pretrain_batch(cfg, batch, seed=0):
    rs = np.random.RandomState(seed)
    s, P = cfg.seq_len, cfg.max_predictions_per_seq
    src = rs.randint(0, cfg.vocab_size, size=(batch, s)).astype(np.int64)
    sent = rs.randint(0, cfg.type_vocab_size,
                      size=(batch, s)).astype(np.int64)
    lens = rs.randint(s // 2, s + 1, size=batch)
    mask = np.zeros((batch, s), np.float32)
    for i, L in enumerate(lens):
        mask[i, :L] = 1.0
    # flat positions into [b*s]
    mpos = np.zeros((batch, P), np.int64)
    mlab = rs.randint(0, cfg.vocab_size, size=(batch, P)).astype(np.int64)
    mw = np.zeros((batch, P), np.float32)
    for i in range(batch):
        n_pred = int(rs.randint(1, P + 1))
        pos = rs.choice(max(2, lens[i]), size=n_pred, replace=False)
        mpos[i, :n_pred] = i * s + pos
        mw[i, :n_pred] = 1.0
    nsp = rs.randint(0, 2, size=(batch, 1)).astype(np.int64)
    return {"src_ids": src, "sent_ids": sent, "input_mask": mask,
            "mask_pos": mpos, "mask_label": mlab, "mask_weight": mw,
            "nsp_label": nsp}
