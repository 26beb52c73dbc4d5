"""exe_build_s: sum of Executor.aot_artifacts() build_seconds: trace+lower+compile, or load from the executable store."""

def read(ctx):
    return sum(a["build_seconds"] or 0.0 for a in ctx["artifacts"])
