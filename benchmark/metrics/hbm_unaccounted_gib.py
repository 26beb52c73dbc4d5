"""hbm_unaccounted_gib: the fullest device after the window by the benchmark's own rule (the larger of peak_bytes_in_use and bytes_in_use + peak_bytes_reserved, from Executor.telemetry()['memory']['devices']) less hbm_state_gib, hbm_step_temp_gib and the step's feed: what the peak holds that the step does not explain (other executables' temporaries, the harness's own copies, fragmentation); may be negative; silent where the program gives no memory account or the device no statistics."""

from benchmark.metrics.hbm_state_gib import (GIB, state_bytes,
                                             window_executable)


def device_peak_bytes(stats):
    """run.py's ``device_peak_bytes`` on the allocator's numbers as
    the program's telemetry carries them."""
    return max(stats.get("peak_bytes_in_use") or 0,
               (stats.get("bytes_in_use") or 0)
               + (stats.get("peak_bytes_reserved") or 0))


def read(ctx):
    step = window_executable(ctx)
    if step is None or not step.get("state") or not step.get("memory"):
        return None
    peak = max(map(device_peak_bytes,
                   ctx["telemetry_after"]["memory"]["devices"]),
               default=0)
    if not peak:
        return None
    return (peak - state_bytes(step) - step["memory"]["temp_bytes"]
            - step["state"]["feed_bytes"]) / GIB
