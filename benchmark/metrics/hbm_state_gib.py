"""hbm_state_gib: the state the window's step takes as arguments, per device: parameters + optimizer accumulators + the rest (AMP's scale, counters, the learning rate), from Executor.telemetry()['memory']['executables'], the executable whose dispatches grew most over the window; counted where the executable is built; silent where the program gives no memory account."""

GIB = 2.0 ** 30


def window_executable(ctx):
    """The record, in Executor.telemetry()["memory"]["executables"]
    after the window, of the executable whose ``dispatches`` grew most
    over it: the window's step. Nothing where the program gives no
    memory account (a commit before it) or nothing was dispatched."""
    after = (ctx["telemetry_after"].get("memory") or {}).get(
        "executables")
    if not after:
        return None
    key = lambda r: (r["entry"], r["program_uid"], r["shape_key"])
    before = {key(r): r["dispatches"] for r in (
        ctx["telemetry_before"].get("memory") or {}).get(
            "executables", ())}
    grown, step = max(((r["dispatches"] - before.get(key(r), 0), r)
                       for r in after), key=lambda g: g[0])
    return step if grown > 0 else None


def state_bytes(step):
    """Bytes of every kind of state the step takes, per device."""
    return sum(step["state"][kind]["bytes"] for kind in (
        "parameters", "optimizer_state", "other"))


def read(ctx):
    step = window_executable(ctx)
    if step is None or not step.get("state"):
        return None
    return state_bytes(step) / GIB
