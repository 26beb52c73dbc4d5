"""device_idle_share: 1 - union of device-op intervals over the traced window, averaged over the chips."""

def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
