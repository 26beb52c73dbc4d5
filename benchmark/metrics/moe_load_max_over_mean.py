"""moe_load_max_over_mean: the busiest held expert's tokens over the held experts' mean, each summed per layer and step over the window (telemetry()['moe'] held_load_max_total / held_load_mean_total); silent where the program counts none."""

from benchmark.metrics.moe_held_share import moe_delta


def read(ctx):
    d = moe_delta(ctx)
    if not d or not d["held_load_mean_total"]:
        return None
    return d["held_load_max_total"] / d["held_load_mean_total"]
