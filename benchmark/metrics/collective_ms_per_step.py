"""collective_ms_per_step: union of the collective ops' intervals on a device over the steps traced; silent on one chip."""

def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["collective_s"] or not ctx["steps_traced"]:
        return None
    return tr["collective_s"] / ctx["steps_traced"] * 1e3
