"""step_mfu: required FLOPs of the batch's real tokens (flops.py) x steps per second of the window, over chips x the bf16 peak."""

def read(ctx):
    if not ctx["peak"]:
        return None
    steps_per_s = ctx["steps"] / ctx["window_s"]
    return 100.0 * ctx["flops_per_step"] * steps_per_s \
        / (ctx["chips"] * ctx["peak"]["bf16_flops"])
