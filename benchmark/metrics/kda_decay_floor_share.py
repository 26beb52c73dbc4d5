"""kda_decay_floor_share: elements of the KDA layers' in-chunk cumulative log decay below -80 (where exp leaves float32's normal range and a factorised chunk form must have re-based) over all of them, over the window: telemetry()['kda'] decay_floor_hits_total over tokens_total x the KDA width (heads x head_dim); silent where the program counts no KDA tokens."""

def read(ctx):
    before = ctx["telemetry_before"].get("kda")
    after = ctx["telemetry_after"].get("kda")
    if not before or not after:
        return None
    tokens = after["tokens_total"] - before["tokens_total"]
    if not tokens:
        return None
    la = ctx["args"]["linear_attn_config"]
    hits = after["decay_floor_hits_total"] \
        - before["decay_floor_hits_total"]
    return 100.0 * hits / (tokens * la["num_heads"] * la["head_dim"])
