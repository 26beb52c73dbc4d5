"""``benchmark/flops_deepseek_v3.py`` against counts made by hand at a
tiny size and at the published widths, ``kernels_least_seconds``
against its two terms, and the readers of the two metrics the Kanana-2
cell adds against contexts made by hand (one without the program's
counters, one with nothing traced)."""

import json
import os

import pytest

from benchmark import flops_afmoe, flops_deepseek_v3, flops_kimi_linear
from benchmark.metrics import dsv3_kernels_roofline, moe_rows_passed_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ARGS = {"hidden_size": 4, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "num_attention_heads": 2,
        "kv_lora_rank": 5, "qk_nope_head_dim": 3, "qk_rope_head_dim": 2,
        "v_head_dim": 2, "intermediate_size": 5,
        "moe_intermediate_size": 3, "vocab_size": 7,
        "n_routed_experts": 2, "num_experts_published": 8,
        "num_experts_per_tok": 2, "n_shared_experts": 2,
        "moe_row_capacity": 20}


def test_step_flops_by_hand():
    # multiply-adds a token. MLA: q 4 x 2 x 5, kv_a 4 x 7, kv_b
    # 5 x 2 x 5, out 2 x 2 x 4
    mla = 40 + 28 + 50 + 16
    # dense MLP 3 x 4 x 5; expert layer: router 4 x 8, the shared pair
    # 3 x 4 x 6, routed 2 x 2 / 8 = half an expert a token
    dense, expert, head = 60, 32 + 72 + 18, 28
    macs = 5 * (3 * mla + dense + 2 * expert + head)
    # QK^T over 5 lanes and PV over 2, 2 heads, 15 causal pairs, in
    # EVERY layer
    macs += 3 * 2 * (5 + 2) * 15
    assert flops_deepseek_v3.step_flops(ARGS, [5]) == 3 * 2 * macs
    assert flops_deepseek_v3.step_flops(ARGS, [5, 0]) \
        == flops_deepseek_v3.step_flops(ARGS, [5])


def test_step_flops_at_the_published_widths():
    """ISSUE 34's arithmetic: an MLA mixer 26.35M parameters, 0.52
    GFLOP a token forward in products and 0.42 in causal pairs, about
    23 TFLOP a step."""
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "kanana2_ep8.json")))
    mla = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert mla == pytest.approx(26.35e6, rel=1e-3)
    moe = 2048 * 128 + 3 * 2048 * 768 * (2 + 6 * 16 / 128)
    token = 5 * mla + 3 * 2048 * 6144 + 4 * moe + 2048 * 16032
    pairs = 8192 * 8193 // 2
    want = 6 * (8192 * token + 5 * 32 * 320 * pairs)
    got = flops_deepseek_v3.step_flops(cfg["args"], [8192])
    assert got == want
    assert got == pytest.approx(22.9e12, rel=0.01)
    assert 2 * token == pytest.approx(0.51e9, rel=0.02)
    assert 2 * 5 * 32 * 320 * pairs / 8192 == pytest.approx(0.42e9,
                                                            rel=0.01)


PEAK = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}


def test_least_seconds_is_its_two_terms():
    """Every layer's latent-attention site and every EXPERT layer's
    grouped products: the costs the two other sparse configurations
    count by, not copies."""
    assert flops_deepseek_v3.mla_flash_cost \
        is flops_kimi_linear.mla_flash_cost
    assert flops_deepseek_v3.gmm_cost is flops_afmoe.gmm_cost
    mla = flops_kimi_linear.mla_flash_cost([5], 2, 5, 2)
    gmm = flops_afmoe.gmm_cost(6, 2, 4, 3)
    least = lambda c: max(c[0] / 100.0, c[1] / 10.0)    # noqa: E731
    got = flops_deepseek_v3.kernels_least_seconds(ARGS, [5], 6, PEAK)
    assert got == pytest.approx(3 * least(mla) + 2 * least(gmm))


def test_least_seconds_at_the_cells_size():
    """The flash calls are compute-bound (12.56 ms a site against 1.23
    of traffic) and five sites are nine tenths of the share."""
    from benchmark import peaks
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "kanana2_ep8.json")))
    peak = peaks.peak("TPU v5 lite")
    fl, by = flops_kimi_linear.mla_flash_cost([8192], 32, 192, 128)
    assert fl / peak["bf16_flops"] == pytest.approx(12.56e-3, rel=1e-3)
    assert by / peak["hbm_bytes_per_s"] == pytest.approx(1.23e-3, rel=1e-2)
    got = flops_deepseek_v3.kernels_least_seconds(
        cfg["args"], [8192], 9000, peak)
    assert got == pytest.approx(68.0e-3, rel=0.01)


def _ctx(counters=True):
    names = ("assignments_total", "assignments_held_total",
             "rows_computed_total", "rows_over_capacity_total",
             "rows_passed_total")
    moe0 = dict.fromkeys(names, 0.0)
    moe1 = dict(moe0, assignments_total=400.0, assignments_held_total=48.0,
                rows_computed_total=64.0, rows_passed_total=96.0)
    tel0, tel1 = ({"moe": moe0}, {"moe": moe1}) if counters else ({}, {})
    return {"telemetry_before": tel0, "telemetry_after": tel1,
            "trace": {"mosaic_s": 8.0, "busy_s": 20.0}, "steps_traced": 4,
            "steps": 4, "peak": PEAK, "args": ARGS,
            "batch_stats": {"lengths": [5]}}


def test_readers_by_hand():
    ctx = _ctx()
    # 48 held assignments over 4 steps x 2 expert layers: 6 rows a layer
    least = flops_deepseek_v3.kernels_least_seconds(ARGS, [5], 6.0, PEAK)
    assert dsv3_kernels_roofline.read(ctx) \
        == pytest.approx(100.0 * least / 2.0)
    # 96 rows walked of 2 expert layers x 4 steps x 20 rows of buffer
    assert moe_rows_passed_share.read(ctx) == pytest.approx(60.0)


@pytest.mark.parametrize("reader", [dsv3_kernels_roofline,
                                    moe_rows_passed_share])
def test_readers_are_silent_with_nothing_to_read(reader):
    """A telemetry without the counters a reader takes (the parent's
    has no ``rows_passed_total`` before PR 31, and no ``moe`` at all
    for a dense model), or a run that traced nothing: nothing is read
    and nothing raises."""
    assert reader.read(_ctx(counters=False)) is None
    ctx = _ctx()
    for tel in (ctx["telemetry_before"], ctx["telemetry_after"]):
        del tel["moe"]["rows_passed_total"]
    if reader is moe_rows_passed_share:
        assert reader.read(ctx) is None
        assert reader.read(dict(_ctx(), args=dict(
            ARGS, moe_row_capacity=None))) is None
    ctx = _ctx()
    ctx["trace"] = None
    if reader is dsv3_kernels_roofline:
        assert reader.read(ctx) is None


def test_benchmark_json_names_the_cell_and_its_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, = [w for w in bench["workloads"]
             if w["name"] == "kanana2_s8k_scan"]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("kanana2_ep8", "lm_s8192_resident_scan8", 1)
    listed = {m["name"] for m in bench["per_layer"]
              if "kanana2_s8k_scan" in m.get("workloads", ())}
    assert listed == {"dsv3_kernels_roofline", "moe_rows_passed_share",
                      "xla_busy_ms_per_step", "moe_held_share",
                      "moe_load_max_over_mean", "gmm_pad_share",
                      "pallas_ms_per_step"}
    cfg, = [c for c in bench["configs"] if c["name"] == "kanana2_ep8"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    # every width of the catalog's row, under the same key
    row = json.load(open(os.path.join(ROOT, cfg["file"])))
    for key, value in {
            "hidden_size": 2048, "intermediate_size": 6144,
            "kv_lora_rank": 512, "moe_intermediate_size": 768,
            "num_attention_heads": 32, "num_key_value_heads": 32,
            "num_experts_per_tok": 6, "n_shared_experts": 2,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "qk_head_dim": 192, "v_head_dim": 128, "head_dim": 64,
            "routed_scaling_factor": 2.448, "rope_theta": 1000000,
            "rope_interleave": True, "rms_norm_eps": 1e-6,
            "first_k_dense_replace": 1, "n_group": 1, "topk_group": 1,
            "max_position_embeddings": 32768}.items():
        assert row[key] == value and row["args"].get(key, value) == value
    assert row["num_experts_published"] == 128
    assert row["reduced_from"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 128,
                                   "vocab_size": 128256}
    assert (row["num_hidden_layers"], row["n_routed_experts"],
            row["vocab_size"]) == (5, 16, 16032)
