"""moe_rows_passed_share: rows the held experts' chunk loops walked over the rows of their buffer, over the window (telemetry()['moe'] rows_passed_total over expert layers x steps x moe_row_capacity): 25% is one chunk of 12,288 in every layer and step at a capacity of 49,152, more says how often a second ran; silent where the program counts none or the configuration names no capacity."""

from benchmark.metrics.moe_held_share import moe_delta


def read(ctx):
    d, args = moe_delta(ctx), ctx["args"]
    capacity = args.get("moe_row_capacity")
    if not d or not d.get("rows_passed_total") or not ctx["steps"] \
            or not capacity:
        return None
    n_moe = args["num_hidden_layers"] - args["first_k_dense_replace"]
    return 100.0 * d["rows_passed_total"] \
        / (n_moe * ctx["steps"] * capacity)
