"""host_settle_ms_per_step: Executor.telemetry() settle_seconds_total over steps, window only: write-back of every persistable to the scope and fetch conversion (RecordEvent executor_settle)."""

from benchmark.metrics.host_entry_ms_per_step import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "settle_seconds_total")
