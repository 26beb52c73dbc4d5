"""The benchmark's FLOP and byte arithmetic against hand counts at a
tiny size: pad positions earn nothing, BERT's masked-LM head is paid at
the predicted positions only."""

import pytest

from benchmark import flops

TFM = {"d_model": 4, "d_ffn": 8, "n_layer": 2, "tgt_vocab": 10,
       "n_head": 2}
BERT = {"hidden_size": 4, "intermediate_size": 8,
        "num_hidden_layers": 2, "vocab_size": 10}


def test_transformer_one_token_pair_by_hand():
    # one pair of length 1, per layer: encoder 4 projections (2*4*4
    # each) + ffn (2*2*4*8) + attention QK^T and PV over 1x1 (2*2*4);
    # decoder the same plus cross q/out (2 proj) + cross k/v (2 proj)
    # + two attentions; head 2*4*10
    proj, ffn, att = 2 * 4 * 4, 2 * 2 * 4 * 8, 2 * 2 * 4
    enc = 4 * proj + ffn + att
    dec = 4 * proj + 4 * proj + ffn + 2 * att
    want = 3 * (2 * (enc + dec) + 2 * 4 * 10)
    assert flops.transformer_step_flops(TFM, [1]) == want


@pytest.mark.parametrize("fn,args", [
    (flops.transformer_step_flops, TFM), (flops.bert_step_flops, BERT)])
def test_pads_earn_nothing(fn, args):
    # the count depends on the sequences' own lengths, never on the
    # length they are padded to; an empty row adds what is paid per
    # row and nothing per position
    a = fn(args, [3, 5], 0)
    b = fn(args, [3, 5, 0], 0)
    per_row = fn(args, [0], 0)
    assert b - a == per_row
    assert per_row <= 3 * (2 * 4 * 4 + 4 * 4)


def test_causal_attention_counts_the_lower_triangle():
    d = TFM["d_model"]
    long, short = (flops.transformer_step_flops(TFM, [n]) for n in (4, 2))
    # subtract what is linear in tokens: twice the length-2 count
    # leaves the attention terms' curvature alone
    quad = long - 2 * short
    # per layer: enc 4*L^2*d, cross 4*L^2*d, causal 4*L(L+1)/2*d
    def att(n):
        return 4 * n * n * d * 2 + 4 * (n * (n + 1) // 2) * d
    assert quad == 3 * TFM["n_layer"] * (att(4) - 2 * att(2))


def test_bert_head_is_paid_at_predicted_positions_only():
    base = flops.bert_step_flops(BERT, [6, 6], 0)
    one = flops.bert_step_flops(BERT, [6, 6], 1)
    assert one - base == 3 * (2 * 4 * 4 + 2 * 4 * 10)
    # bench.py's original pays the head at all S positions: at S=512
    # and 80 predictions that is 6.4 times the required head
    assert flops.bert_step_flops(BERT, [6, 6], 12) - base \
        == 12 * (one - base)


def test_bert_layer_by_hand():
    # one row of length 2, no prediction: per layer per token 4
    # projections + ffn, attention 4*L*L*d; pooler 2*d*d + nsp 2*d*2
    layer = 2 * (4 * 2 * 4 * 4 + 2 * 2 * 4 * 8) + 4 * 2 * 2 * 4
    want = 3 * (2 * layer + 2 * 4 * 4 + 4 * 4)
    assert flops.bert_step_flops(BERT, [2], 0) == want


def test_flash_cost_by_hand():
    fl, by = flops.flash_1k_cost(sites=3, batch=2, n_head=4, sq=8, sk=8,
                                 dh=16)
    assert fl == 3 * 8 * 7 * 2 * 8 * 8 * 16
    # forward q k v o, backward q k v o do dq dk dv: 12 [8, 16] bf16
    assert by == 3 * 8 * 12 * 8 * 16 * 2


def _flash_ctx(seq, mosaic_s=0.03):
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "benchmark", "configs",
                           "transformer_base.json")) as f:
        config = json.load(f)
    return {"trace": {"mosaic_s": mosaic_s}, "steps_traced": 1,
            "config": config, "args": config["args"], "chips": 1,
            "traffic": {"batch": 128, "seq_len": seq},
            "peak": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}


def test_flash_roofline_counts_its_sites_from_the_configuration():
    from benchmark.metrics import flash_roofline
    ctx = _flash_ctx(256)
    # 3 sites a layer x 6 layers, 128 rows x 8 heads of 64
    fl, by = flops.flash_1k_cost(18, 128, 8, 256, 256, 64)
    least = max(fl / 197e12, by / 819e9)
    assert flash_roofline.read(ctx) == pytest.approx(
        100 * least / 0.03)


@pytest.mark.parametrize("ctx", [
    _flash_ctx(512),                        # past the pair's reach
    _flash_ctx(256, mosaic_s=0.0),          # no Mosaic call traced
    dict(_flash_ctx(256), config={})])      # a model without the pair
def test_flash_roofline_is_silent_where_there_is_nothing_to_read(ctx):
    from benchmark.metrics import flash_roofline
    assert flash_roofline.read(ctx) is None
