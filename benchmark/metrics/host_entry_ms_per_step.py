"""host_entry_ms_per_step: Executor.telemetry() entry_seconds_total over steps, window only: the whole of run / run_repeated / run_pipelined on the host clock, entry to return (RecordEvent executor_entry); never a device time."""

def per_step_ms(ctx, counter):
    """A host-seconds counter of Executor.telemetry(), window only,
    over the window's steps; None from a program without it."""
    a, b = ctx["telemetry_before"], ctx["telemetry_after"]
    steps = b["steps"] - a["steps"]
    if steps <= 0 or counter not in b:
        return None
    return (b[counter] - a[counter]) / steps * 1e3


def read(ctx):
    return per_step_ms(ctx, "entry_seconds_total")
