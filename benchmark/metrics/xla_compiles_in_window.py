"""xla_compiles_in_window: Executor.telemetry() xla_compiles after the window minus before it; anything but 0 also fails the run."""

def read(ctx):
    return ctx["telemetry_after"]["xla_compiles"] \
        - ctx["telemetry_before"]["xla_compiles"]
