"""host_dispatch_ms_per_step: Executor.telemetry() dispatch_seconds_total over steps, window only: host enqueue time, never a device time."""

def read(ctx):
    a, b = ctx["telemetry_before"], ctx["telemetry_after"]
    steps = b["steps"] - a["steps"]
    if steps <= 0:
        return None
    return (b["dispatch_seconds_total"] - a["dispatch_seconds_total"]) \
        / steps * 1e3
