"""xla_busy_ms_per_step: the device's busy time outside Mosaic custom calls over the steps traced (busy_s - mosaic_s): XLA's own fusions, where the KDA core lives until it has a kernel; silent where nothing was traced."""

def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx["steps_traced"]:
        return None
    return (tr["busy_s"] - tr["mosaic_s"]) / ctx["steps_traced"] * 1e3
