"""gmm_pad_share: rows the grouped products computed as tile padding over the rows they computed, over the window (telemetry()['moe']: rows_computed_total against the held assignments that found room); silent where the program counts none."""

from benchmark.metrics.moe_held_share import moe_delta


def read(ctx):
    d = moe_delta(ctx)
    if not d or not d["rows_computed_total"]:
        return None
    real = d["assignments_held_total"] - d["rows_over_capacity_total"]
    return 100.0 * (d["rows_computed_total"] - real) \
        / d["rows_computed_total"]
