"""hbm_step_temp_gib: the compiler's count of the temporaries of the window's step (memory_analysis() temp bytes, per device), from Executor.telemetry()['memory']['executables'], the executable whose dispatches grew most over the window; the same for a compiled and a store-loaded executable; silent where the program gives no memory account."""

from benchmark.metrics.hbm_state_gib import GIB, window_executable


def read(ctx):
    step = window_executable(ctx)
    if step is None or not step.get("memory"):
        return None
    return step["memory"]["temp_bytes"] / GIB
