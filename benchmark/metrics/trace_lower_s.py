"""trace_lower_s: Executor.telemetry() build_phases.trace_lower_seconds, every executable of the run: Python-tracing the program's ops through run_block and lowering to StableHLO (jitfn.lower)."""

def read(ctx):
    phases = ctx["telemetry_after"].get("build_phases")
    if phases is None:
        return None         # a program without the counter: no reading
    return phases["trace_lower_seconds"]
