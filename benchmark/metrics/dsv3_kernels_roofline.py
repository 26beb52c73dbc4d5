"""dsv3_kernels_roofline: least time the chip could take for a step's Mosaic calls (flops_deepseek_v3: every layer's latent-attention flash calls by their causal pairs at 192 + 128 lanes, the grouped products by the rows counted, each the larger of FLOPs over the peak and bytes over the bandwidth) over their traced time a step; silent when the trace holds no Mosaic call or the program counts no rows."""

from benchmark import flops_deepseek_v3
from benchmark.metrics.moe_held_share import moe_delta


def read(ctx):
    tr, d = ctx["trace"], moe_delta(ctx)
    if not tr or not tr["mosaic_s"] or not ctx["steps_traced"] \
            or not d or not d["assignments_total"] or not ctx["steps"] \
            or not ctx["peak"]:
        return None
    args = ctx["args"]
    n_moe = args["num_hidden_layers"] - args["first_k_dense_replace"]
    rows = (d["assignments_held_total"] - d["rows_over_capacity_total"]) \
        / (ctx["steps"] * n_moe)
    least = flops_deepseek_v3.kernels_least_seconds(
        args, ctx["batch_stats"]["lengths"], rows, ctx["peak"])
    return 100.0 * least / (tr["mosaic_s"] / ctx["steps_traced"])
