"""store_load_s: Executor.telemetry() build_phases.store_load_seconds, every executable of the run: reading, deserializing and loading executables from the store (cache.get); 0.0 in a cold run."""

def read(ctx):
    phases = ctx["telemetry_after"].get("build_phases")
    if phases is None:
        return None         # a program without the counter: no reading
    return phases["store_load_seconds"]
