"""pad_share: pad positions over all positions of the fed batch, from the generator's masks."""

def read(ctx):
    s = ctx["batch_stats"]
    return 100.0 * s["pad_positions"] / s["positions"]
