"""collective_exposed_share: the part of the collectives' time during which no other op runs on that device, over the collectives' time."""

def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["collective_s"]:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["collective_s"]
