"""slowest_dispatch_over_median: the slowest completion-to-completion interval of the window over the median one (the first, which holds the pipeline's fill, left out)."""

import statistics


def read(ctx):
    iv = ctx["intervals"][1:]
    if len(iv) < 3:
        return None
    return max(iv) / statistics.median(iv)
