"""``benchmark/flops_kimi_linear.py`` against counts made by hand at a
tiny size and at the cell's own, and the readers of the three metrics
the Kimi Linear cell adds against contexts made by hand (one without
the program's counters, one with nothing traced)."""

import json
import os

import pytest

from benchmark import flops_afmoe, flops_kimi_linear
from benchmark.metrics import (kda_decay_floor_share,
                               kimi_kernels_roofline,
                               xla_busy_ms_per_step)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ARGS = {"hidden_size": 4, "num_hidden_layers": 3,
        "first_k_dense_replace": 1,
        "linear_attn_config": {"kda_layers": [1, 3],
                               "full_attn_layers": [2], "num_heads": 2,
                               "head_dim": 3,
                               "short_conv_kernel_size": 4},
        "kda_gate_rank": 2, "num_attention_heads": 2, "kv_lora_rank": 5,
        "qk_nope_head_dim": 3, "qk_rope_head_dim": 1, "v_head_dim": 2,
        "intermediate_size": 5, "moe_intermediate_size": 3,
        "vocab_size": 7, "num_experts": 2, "num_experts_published": 8,
        "num_experts_per_token": 2, "num_shared_experts": 1}


def test_step_flops_by_hand():
    # multiply-adds a token. KDA (width 2 x 3 = 6): q, k, v, out
    # 4 x 4 x 6; two low-rank pairs 2 x (4 x 2 + 2 x 6); beta 4 x 2;
    # three 4-tap convolutions 3 x 6 x 4; the recurrence 3 x 2 x 3 x 3
    kda = 96 + 40 + 8 + 72 + 54
    # MLA: q 4 x 2 x 4, kv_a 4 x 6, kv_b 5 x 2 x 5, out 2 x 2 x 4
    mla = 32 + 24 + 50 + 16
    # dense MLP 3 x 4 x 5; expert layer: router 4 x 8, shared 3 x 4 x 3,
    # routed 2 x 2 / 8 = half an expert a token
    dense, expert, head = 60, 32 + 36 + 18, 28
    macs = 5 * (2 * kda + mla + dense + 2 * expert + head)
    # QK^T over 4 lanes and PV over 2, 2 heads, 15 causal pairs
    macs += 2 * (4 + 2) * 15
    assert flops_kimi_linear.step_flops(ARGS, [5]) == 3 * 2 * macs
    assert flops_kimi_linear.step_flops(ARGS, [5, 0]) \
        == flops_kimi_linear.step_flops(ARGS, [5])


def test_step_flops_at_the_cells_size():
    """ISSUE 32's arithmetic: 0.78 GFLOP a token forward with the
    causal pairs, 19 TFLOP a step."""
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "kimi_linear_ep32.json")))
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 \
        + 3 * 4096 * 4 + 3 * 32 * 128 * 128
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    moe = 2304 * 256 + 3 * 2304 * 1024 * (1 + 8 * 8 / 256)
    token = 4 * kda + mla + 3 * 2304 * 9216 + 4 * moe + 2304 * 20480
    pairs = 8192 * 8193 // 2
    want = 6 * (8192 * token + 32 * 320 * pairs)
    got = flops_kimi_linear.step_flops(cfg["args"], [8192])
    assert got == want
    assert got == pytest.approx(18.9e12, rel=0.01)
    assert 2 * (token + 32 * 320 * pairs / 8192) \
        == pytest.approx(0.768e9, rel=0.01)


def test_kernel_costs_by_hand():
    fl, by = flops_kimi_linear.mla_flash_cost([5], 2, 4, 2)
    # forward QK^T (4) and PV (2); backward QK^T again, dQ, dK (4
    # each), dV, dP (2 each): 4 x 4 + 3 x 2 lanes a pair and head
    assert fl == 2 * 15 * (4 * 4 + 3 * 2) * 2
    # bf16: q, k and their gradients 3 x 4 lanes each, v, o and theirs
    # 3 x 2 each, 5 tokens, 2 heads
    assert by == 2 * 5 * 2 * (2 * 3 * 4 + 2 * 3 * 2)
    # equal widths: the blocked kernels' own count at equal q and kv
    # heads
    assert flops_kimi_linear.mla_flash_cost([9, 4], 3, 8, 8) \
        == flops_afmoe.blocked_flash_cost([9, 4], 3, 3, 8, 0)


PEAK = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}


def test_least_seconds_sums_the_mosaic_families():
    mla = flops_kimi_linear.mla_flash_cost([5], 2, 4, 2)
    gmm = flops_afmoe.gmm_cost(6, 2, 4, 3)
    least = lambda c: max(c[0] / 100.0, c[1] / 10.0)    # noqa: E731
    got = flops_kimi_linear.kernels_least_seconds(ARGS, [5], 6, PEAK)
    assert got == pytest.approx(least(mla) + 2 * least(gmm))


def _ctx(counters=True):
    moe0 = dict.fromkeys(("assignments_total", "assignments_held_total",
                          "rows_computed_total",
                          "rows_over_capacity_total"), 0.0)
    moe1 = dict(moe0, assignments_total=400.0, assignments_held_total=48.0,
                rows_computed_total=64.0)
    tel0, tel1 = {}, {}
    if counters:
        tel0 = {"moe": moe0,
                "kda": {"tokens_total": 10.0, "chunks_total": 2.0,
                        "decay_floor_hits_total": 6.0}}
        tel1 = {"moe": moe1,
                "kda": {"tokens_total": 50.0, "chunks_total": 10.0,
                        "decay_floor_hits_total": 30.0}}
    return {"telemetry_before": tel0, "telemetry_after": tel1,
            "trace": {"mosaic_s": 8.0, "busy_s": 20.0}, "steps_traced": 4,
            "steps": 4, "peak": PEAK, "args": ARGS,
            "batch_stats": {"lengths": [5]}}


def test_readers_by_hand():
    ctx = _ctx()
    # 24 hits over 40 tokens x 6 channels
    assert kda_decay_floor_share.read(ctx) == pytest.approx(10.0)
    # (20 - 8) s outside Mosaic calls over 4 steps
    assert xla_busy_ms_per_step.read(ctx) == pytest.approx(3000.0)
    # 48 held assignments over 4 steps x 2 expert layers: 6 rows a layer
    least = flops_kimi_linear.kernels_least_seconds(ARGS, [5], 6.0, PEAK)
    assert kimi_kernels_roofline.read(ctx) \
        == pytest.approx(100.0 * least / 2.0)


@pytest.mark.parametrize("reader", [kda_decay_floor_share,
                                    kimi_kernels_roofline,
                                    xla_busy_ms_per_step])
def test_readers_are_silent_with_nothing_to_read(reader):
    """A telemetry without the counters a reader takes, or a run that
    traced nothing: nothing is read and nothing raises."""
    if reader is not xla_busy_ms_per_step:      # it reads the trace alone
        assert reader.read(_ctx(counters=False)) is None
    ctx = _ctx()
    ctx["trace"] = None
    if reader is not kda_decay_floor_share:     # it reads no trace
        assert reader.read(ctx) is None


def test_benchmark_json_names_the_cell_and_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"]
             if w["name"] == "kimi_linear_s8k_scan"]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("kimi_linear_ep32", "lm_s8192_resident_scan8", 1)
    listed = {m["name"] for m in bench["per_layer"]
              if "kimi_linear_s8k_scan" in m.get("workloads", ())}
    assert listed == {"kimi_kernels_roofline", "xla_busy_ms_per_step",
                      "kda_decay_floor_share", "moe_held_share",
                      "moe_load_max_over_mean", "gmm_pad_share",
                      "pallas_ms_per_step"}
    cfg, = [c for c in bench["configs"] if c["name"] == "kimi_linear_ep32"]
    assert cfg["reduced"] == ["num_hidden_layers", "linear_attn_config",
                              "num_experts", "vocab_size"]
    # every width of the catalog's row, under the same key
    row = json.load(open(os.path.join(ROOT, cfg["file"])))
    for key, value in {
            "hidden_size": 2304, "intermediate_size": 9216,
            "kv_lora_rank": 512, "moe_intermediate_size": 1024,
            "num_attention_heads": 32, "num_experts_per_token": 8,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "head_dim": 72,
            "routed_scaling_factor": 2.446}.items():
        assert row[key] == value and row["args"].get(key, value) == value
    assert row["linear_attn_config"]["head_dim"] == 128
    assert row["linear_attn_config"]["num_heads"] == 32
