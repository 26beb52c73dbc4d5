"""flash_roofline: least time the chip could take for the step's Mosaic attention calls (flops.flash_1k_cost over the peaks) over their traced time; silent when the trace holds no Mosaic call."""

from benchmark import flops


def read(ctx):
    tr, k = ctx["trace"], ctx["config"].get("flash_1k")
    seq = ctx["traffic"]["seq_len"]
    if not tr or not k or not tr["mosaic_s"] or not ctx["steps_traced"] \
            or seq > k["max_seq"]:
        return None
    args = ctx["args"]
    fl, by = flops.flash_1k_cost(
        k["sites_per_layer"] * args[k["layers_key"]],
        ctx["traffic"]["batch"] // ctx["chips"],
        args[k["heads_key"]], seq, seq,
        args[k["width_key"]] // args[k["heads_key"]])
    peak = ctx["peak"]
    least = max(fl / peak["bf16_flops"], by / peak["hbm_bytes_per_s"])
    return 100.0 * least / (tr["mosaic_s"] / ctx["steps_traced"])
