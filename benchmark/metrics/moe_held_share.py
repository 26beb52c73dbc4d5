"""moe_held_share: assignments to the experts held here over all assignments of the window, from Executor.telemetry()['moe'] (6.25 under uniform routing at 8 of 128); silent where the program counts none."""

def moe_delta(ctx):
    """The held-experts layers' counts over the window: Executor.
    telemetry()["moe"] after it minus before it; nothing where the
    program has no such layer (or no such key)."""
    before = ctx["telemetry_before"].get("moe")
    after = ctx["telemetry_after"].get("moe")
    if not before or not after:
        return None
    return {k: after[k] - before[k] for k in after}


def read(ctx):
    d = moe_delta(ctx)
    if not d or not d["assignments_total"]:
        return None
    return 100.0 * d["assignments_held_total"] / d["assignments_total"]
