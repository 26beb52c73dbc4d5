"""The measured window: whole dispatches, two in flight, all the work
over all the time.

``dispatch()`` enqueues one dispatch and returns a handle; ``read(h)``
blocks until that dispatch has finished and returns its loss. Dispatch
k+1 is enqueued BEFORE the loss of dispatch k is read, as a training
loop reads its loss one step behind: a host pause shorter than a
dispatch never leaves the device without work.

The window starts at the first enqueue and ends when the first dispatch
that completes at or after ``seconds`` completes. The dispatch still in
flight then is drained and checked but counts for nothing. No median,
no best-of, nothing subtracted: a stall inside the window lowers the
rate, as it lowers a user's.
"""

import math
import time


def run_window(dispatch, read, seconds, clock=time.perf_counter):
    """Returns {"completions": seconds since the window's start at
    which each counted dispatch completed, "losses": [...], "drained":
    loss of the uncounted dispatch}."""
    t0 = clock()
    current = dispatch()
    completions, losses = [], []
    while True:
        ahead = dispatch()
        loss = read(current)
        now = clock() - t0
        if not math.isfinite(loss):
            raise FloatingPointError(
                "non-finite loss %r at dispatch %d of the window"
                % (loss, len(losses) + 1))
        completions.append(now)
        losses.append(loss)
        current = ahead
        if now >= seconds:
            break
    drained = read(current)
    if not math.isfinite(drained):
        raise FloatingPointError("non-finite loss %r in the dispatch "
                                 "drained after the window" % drained)
    return {"completions": completions, "losses": losses,
            "drained": drained}


def intervals(completions):
    """Time from one completion to the next; the first from the
    window's start."""
    return [b - a for a, b in zip([0.0] + completions[:-1], completions)]


def rate(completions, steps_per_dispatch, units_per_step, chips):
    """Units (tokens) of every step completed in the window, over the
    window's wall time, over the chips."""
    steps = len(completions) * steps_per_dispatch
    return steps * units_per_step / completions[-1] / chips
