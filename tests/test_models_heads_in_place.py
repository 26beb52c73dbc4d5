"""models.transformer and models.bert hand their projections to the
attention op as they come, [b, s, h * dh] (no head split or merge is
built): the loss and every gradient against benchmark/reference's plain
float32 model at a small size on the CPU, and the training programs
hold no ``transpose2`` under the layer ``attention``."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework
from paddle_tpu.models import bert, transformer

common = importlib.import_module("benchmark.reference.common")
ref_tfm = importlib.import_module("benchmark.reference.transformer_base")
ref_bert = importlib.import_module("benchmark.reference.bert_base")

TFM = dict(src_vocab=61, tgt_vocab=67, max_len=16, d_model=32, d_ffn=64,
           n_head=4, n_layer=2, dropout=0.0, label_smooth_eps=0.1,
           weight_sharing=False)
BERT = dict(vocab_size=97, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=16, type_vocab_size=2,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
            seq_len=16, max_predictions_per_seq=4,
            initializer_range=0.1)


def _tfm_program():
    loss, _, _ = transformer.transformer(
        transformer.TransformerConfig(**TFM))
    return loss


def _bert_program():
    takes = {k: v for k, v in BERT.items() if k != "initializer_range"}
    total, _, _ = bert.bert_pretrain(bert.BertConfig(**takes))
    return total


def _tfm_batch():
    return transformer.make_fake_batch(
        transformer.TransformerConfig(**TFM), 3, seed=1)


def _bert_batch():
    takes = {k: v for k, v in BERT.items() if k != "initializer_range"}
    return bert.make_fake_pretrain_batch(bert.BertConfig(**takes), 3,
                                         seed=1)


def _bert_rows(batch):
    """The reference reads positions in the row, the program flat
    positions into [b * s]."""
    rows = dict(batch)
    rows["mask_pos_in_row"] = batch["mask_pos"] % BERT["seq_len"]
    return rows


MODELS = {
    "transformer": (_tfm_program, _tfm_batch, ref_tfm, TFM, dict),
    "bert_pretrain": (_bert_program, _bert_batch, ref_bert, BERT,
                      _bert_rows),
}


def _built(build):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss = build()
            pg = fluid.append_backward(loss)
    return main, startup, loss, pg


@pytest.mark.parametrize("model", sorted(MODELS))
def test_loss_and_every_gradient_match_the_reference(model):
    """float32 on both sides and no dropout, so what is left is the
    order of the sums: 2e-4 of each leaf's largest gradient, where a
    head read from a neighbour's lanes would read 1. (BERT's key
    biases have no gradient but rounding, a softmax being blind to a
    constant along its keys: those leaves are held to 1e-7 of the
    largest gradient of any leaf.)"""
    build, make_batch, ref, cfg, rows_of = MODELS[model]
    main, startup, loss, pg = _built(build)
    scope, exe = fluid.Scope(), fluid.Executor()
    batch = make_batch()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for n, v in common.init_params(ref.param_spec(cfg), 7).items():
            scope.set_var(n, v)
        out = exe.run(main, feed=batch,
                      fetch_list=[loss] + [g for _, g in pg])
    params = common.init_params(ref.param_spec(cfg), 7)
    assert {p.name for p, _ in pg} == set(params)
    rows = {k: jnp.asarray(v) for k, v in rows_of(batch).items()}
    want, grads = jax.value_and_grad(ref.block_loss)(
        params, rows, ref.normalizers(rows), jax.random.key(0), cfg,
        "f32")
    np.testing.assert_allclose(np.asarray(out[0]).reshape(()), want,
                               rtol=5e-6)
    largest = max(float(jnp.max(jnp.abs(g))) for g in grads.values())
    for (p, _), got in zip(pg, out[1:]):
        scale = float(jnp.max(jnp.abs(grads[p.name])))
        assert scale > 0 or "_att_k.b_0" in p.name, p.name
        np.testing.assert_allclose(
            got, grads[p.name], rtol=0,
            atol=2e-4 * scale + 1e-7 * largest, err_msg=p.name)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_no_transpose_under_attention(model):
    """Forward and backward ops alike (a gradient op inherits its
    forward op's layer): the attention layers are four projections
    and the fused op on rank 3, which carries the head count."""
    main, _, _, _ = _built(MODELS[model][0])
    ops = [op for op in main.global_block().ops
           if framework.innermost_scope(
               op.attrs.get("op_namescope")) == "attention"]
    kinds = {op.type for op in ops}
    assert "scaled_dot_product_attention" in kinds
    assert not {t for t in kinds
                if "transpose" in t or "reshape" in t}, sorted(kinds)
    heads = MODELS[model][3].get("n_head") \
        or MODELS[model][3]["num_attention_heads"]
    for op in ops:
        if op.type == "scaled_dot_product_attention":
            assert op.attrs["num_heads"] == heads
            q = main.global_block().var(op.input("Q")[0])
            assert len(q.shape) == 3
