"""device_busy_ms_per_step: union of device-op intervals over the steps traced."""

def read(ctx):
    tr = ctx["trace"]
    if not tr or not ctx["steps_traced"]:
        return None
    return tr["busy_s"] / ctx["steps_traced"] * 1e3
