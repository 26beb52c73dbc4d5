"""host_prepare_ms_per_step: Executor.telemetry() prepare_seconds_total over steps, window only: feed check, feed_h2d, persistables gathered, signature and executable lookup, fold_in (RecordEvent executor_prepare)."""

from benchmark.metrics.host_entry_ms_per_step import per_step_ms


def read(ctx):
    return per_step_ms(ctx, "prepare_seconds_total")
