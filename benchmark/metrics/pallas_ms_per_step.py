"""pallas_ms_per_step: sum of the Mosaic custom calls' device durations over the steps traced; silent when there is none."""

def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["mosaic_s"] or not ctx["steps_traced"]:
        return None
    return tr["mosaic_s"] / ctx["steps_traced"] * 1e3
