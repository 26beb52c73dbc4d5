"""``benchmark/flops_afmoe.py`` against counts made by hand at a tiny
size, and the readers of the four metrics the Trinity-Mini cell adds
against contexts made by hand (one without the program's counters: a
parent commit's)."""

import json
import os

import pytest

from benchmark import flops_afmoe
from benchmark.metrics import (gmm_pad_share, lm_kernels_roofline,
                               moe_held_share, moe_load_max_over_mean)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ARGS = {"hidden_size": 4, "head_dim": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 5,
        "moe_intermediate_size": 3, "vocab_size": 7,
        "num_hidden_layers": 2, "num_dense_layers": 1,
        "layer_types": ["sliding_attention", "full_attention"],
        "num_experts": 2, "num_experts_published": 4,
        "num_experts_per_tok": 2, "num_shared_experts": 1,
        "sliding_window": 3}


def test_pairs_a_row_may_read():
    assert flops_afmoe.causal_pairs(5) == 15
    assert flops_afmoe.causal_pairs(5, 3) == 1 + 2 + 3 + 3 + 3
    assert flops_afmoe.causal_pairs(3, 3) == 6
    assert flops_afmoe.causal_pairs(2, 3) == 3
    # the cell's sliding layer: 2048 rows of the triangle, then 2048 a
    # row
    assert flops_afmoe.causal_pairs(8192, 2048) \
        == 2048 * 2049 // 2 + 6144 * 2048


def test_step_flops_by_hand():
    # multiply-adds a token: attention q, gate, out 3 x 4 x 4 and k, v
    # 2 x 4 x 2; dense MLP 3 x 4 x 5; expert layer router 4 x 4,
    # shared 3 x 4 x 3, routed 2 of 2 x 2 / 4 = 1 expert a token;
    # head 4 x 7
    attn, dense, expert, head = 64, 60, 16 + 36 + 36, 28
    macs = 5 * (2 * attn + dense + expert + head)
    # QK^T and PV: 2 x heads x head_dim a pair; 12 pairs in the
    # window of 3 over 5 positions, 15 in the full layer
    macs += 2 * 2 * 2 * (12 + 15)
    assert flops_afmoe.step_flops(ARGS, [5]) == 3 * 2 * macs
    # rows earn by their own lengths
    assert flops_afmoe.step_flops(ARGS, [5, 0]) \
        == flops_afmoe.step_flops(ARGS, [5])


def test_kernel_costs_by_hand():
    fl, by = flops_afmoe.blocked_flash_cost([5], 2, 1, 2, 3)
    assert fl == 7 * 2 * 12 * 2 * 2       # 7 contractions, 12 pairs
    # bf16: q, o forward and q, o, do, dq backward at 2 heads; k, v
    # and k, v, dk, dv at 1
    assert by == 2 * (5 * 2) * (6 * 2 + 6 * 1)
    fl, by = flops_afmoe.gmm_cost(10, 2, 4, 3)
    assert fl == 9 * 2 * 10 * 4 * 3
    weights = 3 * 3 * 2 * 4 * 3
    acts = 10 * (7 + 11) * 2 + 10 * (7 + 10)
    assert by == 2 * (weights + acts)
    peak = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e3}
    want = 672 / 1e3 + max(7 * 2 * 15 * 2 * 2, 360) / 1e3 \
        + max(2160, 1492) / 1e3
    assert flops_afmoe.kernels_least_seconds(ARGS, [5], 10, peak) \
        == pytest.approx(want)


def test_the_cells_step_is_what_the_issue_reckoned():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "trinity_mini_ep16.json")))
    total = flops_afmoe.step_flops(cfg["args"], [8192])
    assert 17e12 < total < 18.5e12


def _ctx(before, after, **more):
    return {"telemetry_before": {"moe": before},
            "telemetry_after": {"moe": after}, **more}


def test_readers_of_the_counters():
    zero = dict.fromkeys(
        ("assignments_total", "assignments_held_total",
         "rows_computed_total", "rows_over_capacity_total",
         "held_load_max_total", "held_load_mean_total"), 0.0)
    before = dict(zero, assignments_total=100.0)
    after = dict(zero, assignments_total=1700.0,
                 assignments_held_total=100.0, rows_computed_total=128.0,
                 held_load_max_total=30.0, held_load_mean_total=12.5)
    ctx = _ctx(before, after)
    assert moe_held_share.read(ctx) == pytest.approx(6.25)
    assert moe_load_max_over_mean.read(ctx) == pytest.approx(2.4)
    assert gmm_pad_share.read(ctx) == pytest.approx(100 * 28 / 128)
    # a program without the counters (the parent commit): silent
    for old in ({"telemetry_before": {}, "telemetry_after": {}},
                _ctx(None, None), _ctx(zero, zero)):
        old.update(trace={"mosaic_s": 1.0}, steps_traced=2, steps=2,
                   peak={"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0},
                   args=ARGS, batch_stats={"lengths": [5]})
        for m in (moe_held_share, moe_load_max_over_mean, gmm_pad_share,
                  lm_kernels_roofline):
            assert m.read(old) is None


def test_roofline_reader():
    peak = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e3}
    zero = dict.fromkeys(
        ("assignments_total", "rows_computed_total",
         "rows_over_capacity_total", "held_load_max_total",
         "held_load_mean_total"), 0.0)
    # 4 steps in the window, one expert layer: 10 held rows a step
    ctx = _ctx(dict(zero, assignments_held_total=0.0),
               dict(zero, assignments_held_total=40.0,
                    assignments_total=80.0),
               trace={"mosaic_s": 20.0}, steps_traced=2, steps=4,
               peak=peak, args=ARGS, batch_stats={"lengths": [5]})
    least = flops_afmoe.kernels_least_seconds(ARGS, [5], 10, peak)
    assert lm_kernels_roofline.read(ctx) \
        == pytest.approx(100 * least / 10.0)
    assert lm_kernels_roofline.read(dict(ctx, trace=None)) is None
