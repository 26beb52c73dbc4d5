"""Transformer-base NMT (BASELINE.json config 3).

Reference: the fluid transformer used by its distributed tests
(python/paddle/fluid/tests/unittests/dist_transformer.py) and the
machine-translation benchmark (benchmark/fluid/models/machine_translation
.py) — built here from this framework's layer primitives, TPU-first:

  - static [batch, seq_len] shapes (pad + mask, no LoD) so XLA tiles the
    QK^T / PV matmuls onto the MXU;
  - attention mask folded in as an additive bias (one fused add, no
    boolean select chains);
  - the whole train step (12 blocks fwd + bwd + Adam) compiles to ONE
    XLA program via the Executor;
  - weights annotated for Megatron-style tp sharding on request
    (shard_tp) — GSPMD inserts the ICI collectives;
  - every op is built under a ``name_scope`` naming its layer KIND
    (embedding, attention, ffn, residual_norm, vocab_head, loss — not
    its index: six encoder layers share ``attention``), which is what
    a device trace is charged to (profiler.scope_table).
"""

from __future__ import annotations

import numpy as np

from .. import layers
from ..framework import name_scope
from ..initializer import NumpyArrayInitializer
from ..param_attr import ParamAttr


class TransformerConfig:
    """transformer-base hyperparameters."""

    def __init__(self, src_vocab=30000, tgt_vocab=30000, max_len=256,
                 d_model=512, d_ffn=2048, n_head=8, n_layer=6,
                 dropout=0.1, label_smooth_eps=0.1,
                 weight_sharing=False):
        if d_model % 2:
            raise ValueError("d_model must be even (sin/cos positional "
                             "encoding interleave): got %d" % d_model)
        if d_model % n_head:
            raise ValueError("d_model %d not divisible by n_head %d"
                             % (d_model, n_head))
        if weight_sharing and src_vocab != tgt_vocab:
            raise ValueError(
                "weight_sharing requires src_vocab == tgt_vocab "
                "(got %d vs %d)" % (src_vocab, tgt_vocab))
        self.src_vocab = src_vocab
        self.tgt_vocab = tgt_vocab
        self.max_len = max_len
        self.d_model = d_model
        self.d_ffn = d_ffn
        self.n_head = n_head
        self.n_layer = n_layer
        self.dropout = dropout
        self.label_smooth_eps = label_smooth_eps
        self.weight_sharing = weight_sharing


def _pos_encoding_table(max_len, d_model):
    pos = np.arange(max_len)[:, None].astype(np.float64)
    dim = np.arange(d_model // 2)[None, :].astype(np.float64)
    angle = pos / np.power(10000.0, 2.0 * dim / d_model)
    table = np.zeros((max_len, d_model), dtype=np.float32)
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle)
    return table


@name_scope("attention")
def _multi_head_attention(q_in, kv_in, bias, cfg, is_test, prefix):
    """Scaled dot-product attention over n_head heads.

    bias: additive attention bias [batch, 1, q_len, k_len] (0 where
    attending, -1e9 at masked positions).
    """
    d = cfg.d_model
    h = cfg.n_head
    dh = d // h

    q = layers.fc(q_in, d, num_flatten_dims=2, bias_attr=False,
                  name=prefix + "_q")
    k = layers.fc(kv_in, d, num_flatten_dims=2, bias_attr=False,
                  name=prefix + "_k")
    v = layers.fc(kv_in, d, num_flatten_dims=2, bias_attr=False,
                  name=prefix + "_v")

    # fused attention core (pallas flash kernel when enabled), on the
    # projections' own [b, s, h * dh] layout: the kernel picks the
    # heads out of the lanes, so no head split or merge is built.
    # Attention dropout runs in-kernel (TPU PRNG), so the score matrix
    # never materializes in HBM even when training with dropout
    ctx = layers.scaled_dot_product_attention(
        q, k, v, bias=bias, scale=dh ** -0.5,
        dropout_rate=cfg.dropout, is_test=is_test, num_heads=h)
    return layers.fc(ctx, d, num_flatten_dims=2, bias_attr=False,
                     name=prefix + "_out")


@name_scope("ffn")
def _ffn(x, cfg, prefix):
    hidden = layers.fc(x, cfg.d_ffn, num_flatten_dims=2, act="relu",
                       name=prefix + "_fc1")
    return layers.fc(hidden, cfg.d_model, num_flatten_dims=2,
                     name=prefix + "_fc2")


@name_scope("residual_norm")
def _post_process(x, residual, cfg, is_test, prefix):
    """residual + dropout, then layer_norm (fluid's "da n" cmd chain)."""
    if cfg.dropout and not is_test:
        x = layers.dropout(x, cfg.dropout,
                           dropout_implementation="upscale_in_train")
    out = layers.elementwise_add(x, residual)
    return layers.layer_norm(out, begin_norm_axis=2,
                             name=prefix + "_ln")


@name_scope("embedding")
def _embed(ids, vocab, cfg, is_test, name):
    emb = layers.embedding(
        ids, size=(vocab, cfg.d_model),
        param_attr=ParamAttr(name=name + "_word_emb"))
    emb = layers.scale(emb, scale=cfg.d_model ** 0.5)
    pos_tab = _pos_encoding_table(cfg.max_len, cfg.d_model)
    seq_len = ids.shape[1]
    pos = layers.assign(pos_tab[:seq_len])
    out = layers.elementwise_add(emb, pos)
    if cfg.dropout and not is_test:
        out = layers.dropout(out, cfg.dropout,
                             dropout_implementation="upscale_in_train")
    return out


@name_scope("attention")
def _pad_bias(pad_mask):
    """[b, s] float 1=token 0=pad -> additive bias [b, 1, 1, s]."""
    bias = layers.scale(pad_mask, scale=1e9, bias=-1.0,
                        bias_after_scale=False)  # (m - 1) * 1e9
    return layers.unsqueeze(layers.unsqueeze(bias, [1]), [1])


@name_scope("attention")
def _causal_bias(pad_bias_, seq_len):
    """Combine key-pad bias with a lower-triangular causal bias."""
    causal = np.triu(np.full((seq_len, seq_len), -1e9, np.float32), 1)
    causal_v = layers.assign(causal.reshape(1, 1, seq_len, seq_len))
    return layers.elementwise_add(pad_bias_, causal_v)


def encoder(src_ids, src_mask, cfg, is_test=False):
    x = _embed(src_ids, cfg.src_vocab, cfg, is_test, "src")
    bias = _pad_bias(src_mask)
    for i in range(cfg.n_layer):
        p = "enc%d" % i
        att = _multi_head_attention(x, x, bias, cfg, is_test,
                                    p + "_att")
        x = _post_process(att, x, cfg, is_test, p + "_att")
        ff = _ffn(x, cfg, p + "_ffn")
        x = _post_process(ff, x, cfg, is_test, p + "_ffn")
    return x


def decoder(tgt_ids, enc_out, src_mask, tgt_mask, cfg, is_test=False):
    x = _embed(tgt_ids, cfg.tgt_vocab, cfg, is_test,
               "src" if cfg.weight_sharing else "tgt")
    self_bias = _causal_bias(_pad_bias(tgt_mask), tgt_ids.shape[1])
    cross_bias = _pad_bias(src_mask)
    for i in range(cfg.n_layer):
        p = "dec%d" % i
        att = _multi_head_attention(x, x, self_bias, cfg, is_test,
                                    p + "_self")
        x = _post_process(att, x, cfg, is_test, p + "_self")
        catt = _multi_head_attention(x, enc_out, cross_bias, cfg,
                                     is_test, p + "_cross")
        x = _post_process(catt, x, cfg, is_test, p + "_cross")
        ff = _ffn(x, cfg, p + "_ffn")
        x = _post_process(ff, x, cfg, is_test, p + "_ffn")
    return x


def transformer(cfg: TransformerConfig, is_test=False):
    """Build the full training graph. Declares feeds:
      src_ids/tgt_ids/lbl_ids [b, s] int64; src_mask/tgt_mask [b, s]
      float32 (1=token, 0=pad).
    Returns (avg_cost, token_num, predict_logits).
    """
    s = cfg.max_len
    src_ids = layers.data("src_ids", shape=[s], dtype="int64")
    tgt_ids = layers.data("tgt_ids", shape=[s], dtype="int64")
    lbl_ids = layers.data("lbl_ids", shape=[s], dtype="int64")
    src_mask = layers.data("src_mask", shape=[s], dtype="float32")
    tgt_mask = layers.data("tgt_mask", shape=[s], dtype="float32")

    enc_out = encoder(src_ids, src_mask, cfg, is_test)
    dec_out = decoder(tgt_ids, enc_out, src_mask, tgt_mask, cfg,
                      is_test)

    # Fused head: the [b, s, 30k] logits are the model's largest
    # activation — the fused op never materializes them for the loss,
    # and the uniform label smoothing folds into its closed form. The
    # plain logits (for decoding/inference graphs) come from a separate
    # mul on the same weight that XLA dead-code-eliminates whenever
    # they go unfetched (i.e. every training step). The fused op IS the
    # pre-softmax matmul with the smoothed cross entropy folded in, so
    # ``vocab_head`` holds both here; ``loss`` is the masked mean.
    with name_scope("vocab_head"):
        cost, logits = layers.fused_linear_cross_entropy(
            dec_out, layers.unsqueeze(lbl_ids, [2]), cfg.tgt_vocab,
            epsilon=cfg.label_smooth_eps, name="proj",
            return_logits=True)
    with name_scope("loss"):
        cost = layers.squeeze(cost, [2])            # [b, s]
        weighted = layers.elementwise_mul(cost, tgt_mask)
        sum_cost = layers.reduce_sum(weighted)
        token_num = layers.reduce_sum(tgt_mask)
        avg_cost = layers.elementwise_div(sum_cost, token_num)
    return avg_cost, token_num, logits


def fast_decode(cfg: TransformerConfig, beam_size, max_out_len,
                bos_idx=0, eos_idx=1):
    """Beam-search inference graph (reference: dist_transformer.py
    fast_decode:1498 — while_op + beam_search over LoD-pruned beams
    with per-layer KV caches).

    TPU-first reformulation, fully compiled — the decode loop lowers
    to ONE lax.while_loop (layers.While with dense state only, no
    tensor arrays), so there is no per-step host dispatch:

      - beams are a dense [batch, K] frontier riding a flattened
        batch*K axis through the decoder (ops/beam_search_ops.py
        replaces LoD pruning: finished beams survive as end_id
        continuations);
      - instead of KV caches the prefix buffer [batch*K, T] is
        re-decoded each step and the current position is picked with
        a one-hot time mask — recompute is XLA's preferred trade on
        TPU (static shapes, no growing buffers); O(T^2) total like
        the cached formulation's attention anyway;
      - beam reordering (the reference's sequence_expand by score
        LoD) is a batched one-hot matmul over the beam axis, and the
        history is reordered IN-LOOP so no backtrack pass is needed;
      - ids/masks round-trip through f32 for the arithmetic one-hots
        (exact for vocab < 2^23).

    Run it with the TRAINED scope: parameter names match the training
    graph (enc*/dec*/proj), so ``exe.run(decode_prog, ...)`` after
    training (or after io.load_persistables) just works.

    Declares feeds src_ids/src_mask [batch, cfg.max_len]; returns
    (sentence_ids [batch, K, max_out_len+1] best-first,
    sentence_scores [batch, K]).
    """
    from ..core.enforce import enforce
    K = int(beam_size)
    T = int(max_out_len)
    enforce(T + 1 <= cfg.max_len,
            "max_out_len+1 (%d) exceeds the positional table "
            "(cfg.max_len=%d)" % (T + 1, cfg.max_len))
    s = cfg.max_len
    src_ids = layers.data("src_ids", shape=[s], dtype="int64")
    src_mask = layers.data("src_mask", shape=[s], dtype="float32")

    enc_out = encoder(src_ids, src_mask, cfg, is_test=True)

    # expand encoder state K-fold onto the flattened beam batch
    enc_k = layers.expand(layers.unsqueeze(enc_out, [1]), [1, K, 1, 1])
    enc_k = layers.reshape(enc_k, (-1, s, cfg.d_model))
    src_mask_k = layers.reshape(
        layers.expand(layers.unsqueeze(src_mask, [1]), [1, K, 1]),
        (-1, s))

    # dense loop state, batch-size-agnostic (derived from src_mask)
    zeros_b = layers.scale(layers.reduce_sum(src_mask, dim=1,
                                             keep_dim=True), scale=0.0)
    # scores: beam 0 live, others -inf so step 1 fans out from bos
    init_row = layers.assign(
        np.array([0.0] + [-1e9] * (K - 1), np.float32))
    scores = layers.elementwise_add(zeros_b, init_row)      # [B, K]
    last_ids = layers.cast(
        layers.scale(scores, scale=0.0, bias=float(bos_idx)), "int64")
    hist = layers.cast(layers.expand(
        layers.unsqueeze(layers.scale(scores, scale=0.0,
                                      bias=float(bos_idx)), [2]),
        [1, 1, T + 1]), "int64")                            # [B,K,T+1]

    step = layers.fill_constant([1], "int64", value=1)
    max_c = layers.fill_constant([1], "int64", value=T + 1)
    cond = layers.less_than(step, max_c)

    kidx = layers.assign(np.arange(K, dtype=np.float32))      # [K]
    tidx = layers.assign(np.arange(T + 1, dtype=np.float32))  # [T+1]

    w_proj = layers.create_parameter(
        shape=(cfg.d_model, cfg.tgt_vocab), dtype="float32",
        attr=ParamAttr(name="proj.w_0"))

    loop = layers.While(cond)
    with loop.block():
        tgt = layers.reshape(hist, (-1, T + 1))         # [B*K, T+1]
        tgt_mask = layers.cast(
            layers.scale(layers.cast(tgt, "float32"), scale=0.0,
                         bias=1.0), "float32")
        dec_out = decoder(tgt, enc_k, src_mask_k, tgt_mask, cfg,
                          is_test=True)                 # [B*K,T+1,D]
        # pick position step-1 with an arithmetic one-hot over time
        step_f = layers.cast(step, "float32")
        tmask = layers.relu(
            1.0 - layers.square(tidx - (step_f - 1.0)))  # [T+1]
        cur = layers.reduce_sum(
            dec_out * layers.unsqueeze(tmask, [1]), dim=1)  # [B*K,D]
        logits = layers.matmul(cur, w_proj)             # [B*K, V]
        logp = layers.log(layers.softmax(logits) + 1e-20)
        logp3 = layers.reshape(logp, (-1, K, cfg.tgt_vocab))

        sel_ids, sel_scores, parent = layers.beam_search(
            pre_ids=last_ids, pre_scores=scores, ids=None,
            scores=logp3, beam_size=K, end_id=eos_idx)

        # reorder history by parent (one-hot matmul over the beam
        # axis), then write the new ids at position `step`
        oh = layers.relu(1.0 - layers.square(
            layers.unsqueeze(layers.cast(parent, "float32"), [2])
            - kidx))                                     # [B,K,K]
        hist_f = layers.matmul(oh, layers.cast(hist, "float32"))
        wmask = layers.relu(1.0 - layers.square(tidx - step_f))
        hist_new = hist_f * (1.0 - wmask) + \
            layers.cast(layers.unsqueeze(sel_ids, [2]),
                        "float32") * wmask
        layers.assign(layers.cast(hist_new, "int64"), hist)
        layers.assign(sel_ids, last_ids)
        layers.assign(sel_scores, scores)
        layers.increment(step, value=1)
        # continue while steps remain AND any beam is unfinished
        alive = layers.reduce_sum(layers.cast(
            layers.square(layers.cast(sel_ids, "float32")
                          - float(eos_idx)), "float32"))
        zero_c = layers.fill_constant([1], "float32", value=0.0)
        layers.logical_and(layers.less_than(step, max_c),
                           layers.less_than(zero_c, alive), out=cond)

    # best-first: reorder by final scores
    order_scores, order = layers.topk(scores, K)          # [B, K]
    ooh = layers.relu(1.0 - layers.square(
        layers.unsqueeze(layers.cast(order, "float32"), [2]) - kidx))
    out_ids = layers.cast(
        layers.matmul(ooh, layers.cast(hist, "float32")), "int64")
    return out_ids, order_scores


def shard_tp(program, axis="tp"):
    """Annotate attention/ffn weights Megatron-style over the tp axis:
    q/k/v and ffn fc1 column-parallel, output proj and ffn fc2
    row-parallel; embeddings vocab-sharded. GSPMD then inserts the
    all-reduces the reference would have hand-placed."""
    from ..parallel import shard
    for p in program.all_parameters():
        if len(p.shape) != 2:
            continue
        n = p.name
        if any(t in n for t in ("_q.", "_k.", "_v.", "_fc1.")):
            shard(p, None, axis)
        elif any(t in n for t in ("_out.", "_fc2.")):
            shard(p, axis, None)
        elif "word_emb" in n:
            shard(p, axis, None)       # (vocab, d_model): vocab is dim 0
        elif n.startswith("proj"):
            shard(p, None, axis)       # (d_model, vocab): vocab is dim 1
    return program


def make_fake_batch(cfg, batch, seq_len=None, seed=0):
    """Synthetic padded batch for tests/benchmarks."""
    s = seq_len or cfg.max_len
    rs = np.random.RandomState(seed)
    lens = rs.randint(max(2, s // 2), s + 1, size=batch)
    src = np.zeros((batch, s), np.int64)
    tgt = np.zeros((batch, s), np.int64)
    lbl = np.zeros((batch, s), np.int64)
    smask = np.zeros((batch, s), np.float32)
    tmask = np.zeros((batch, s), np.float32)
    for i, L in enumerate(lens):
        src[i, :L] = rs.randint(1, cfg.src_vocab, size=L)
        tgt[i, :L] = rs.randint(1, cfg.tgt_vocab, size=L)
        lbl[i, :L] = rs.randint(1, cfg.tgt_vocab, size=L)
        smask[i, :L] = 1.0
        tmask[i, :L] = 1.0
    return {"src_ids": src, "tgt_ids": tgt, "lbl_ids": lbl,
            "src_mask": smask, "tgt_mask": tmask}
