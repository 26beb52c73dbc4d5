"""The blocked flash kernels alone, timed on the chip at the sites of
the three 8k cells (ops/pallas/attention.py ``_flash_fwd`` and
``_flash_bwd``):

    mla         32 heads x 8,192 x 192 / 128, causal (kanana2_s8k_scan's
                five sites, kimi_linear_s8k_scan's one)
    gqa         32 q / 4 kv heads x 8,192 x 128, causal
                (trinity_mini_s8k_scan's full layer)
    gqa_window  the same with a window of 2,048 (its sliding layers)

    python tools/blocked_sweep.py                     # the three sites
    python tools/blocked_sweep.py --sites mla --ablate
    python tools/blocked_sweep.py --tiles 1:1024:512 --budget-mb 30
    python tools/blocked_sweep.py --repo .archive_check/parent

One JSON line a kernel and site: the median of ``--calls`` calls, each
closed by ``block_until_ready``, beside the products' FLOPs (what one
recompute needs: 320 lanes a pair forward and 832 backward at 192 /
128), the bytes of the arrays the call reads and writes once, the least
time the chip could take for either, and the schedule the site's shape
chose. ``--ablate`` times each site again with one piece of a loop
step left out, to find the unit that binds: the mask, the exp, the K /
V rows a step reads (always the block's first). Those results are
WRONG and only timed; the pieces are taken out here, by replacing a
name in the kernels' module for the length of a trace, and nothing in
the program can reach them. ``--tiles G:blk_q:blk_k`` and
``--budget-mb`` replace the tile choice and the VMEM model's budget
the same way, to time a schedule the shape would not choose.
``--repo`` times another checkout's kernels (the parent's) with this
file. A tool: no benchmark cell runs it, and it fails off the TPU."""

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

# one v5e: bf16 FLOP/s, HBM bytes/s (benchmark/peaks.py)
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9

SITES = {
    "mla": dict(h=32, hkv=32, s=8192, dqk=192, dv=128, window=0),
    "gqa": dict(h=32, hkv=4, s=8192, dqk=128, dv=128, window=0),
    "gqa_window": dict(h=32, hkv=4, s=8192, dqk=128, dv=128,
                       window=2048),
}


def pairs(s, window):
    """(row, key) pairs of a causal site of length s."""
    if not window or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def cost(site, kernel):
    """(FLOPs, bytes) of one call: the products of one recompute, and
    every array it reads or writes, once."""
    h, hkv, s, dqk, dv = (site[n] for n in ("h", "hkv", "s", "dqk", "dv"))
    lanes = dqk + dv if kernel == "fwd" else 3 * dqk + 2 * dv
    qo, kv = h * s * (dqk + dv) * 2, hkv * s * (dqk + dv) * 2
    stats = h * s * 4
    moved = qo + kv + stats if kernel == "fwd" \
        else 2 * qo + 2 * kv + 2 * stats
    return 2 * pairs(s, site["window"]) * lanes * h, moved


class _Without:
    """``jax.numpy`` with one function replaced by the identity."""

    def __init__(self, module, name):
        self._module, self._name = module, name

    def __getattr__(self, name):
        if name == self._name:
            return lambda x: x
        return getattr(self._module, name)


@contextlib.contextmanager
def replaced(module, **names):
    old = {n: getattr(module, n) for n in names}
    for n, v in names.items():
        setattr(module, n, v)
    try:
        yield
    finally:
        for n, v in old.items():
            setattr(module, n, v)


def ablations(A):
    """{name: what to replace in the kernels' module}."""
    from jax.experimental import pallas as pl
    out = {"mask": dict(_causal_mask=lambda s, *a, **k: s),
           "exp": dict(jnp=_Without(A.jnp, "exp"))}
    if hasattr(A, "_rows"):
        out["kv_rows"] = dict(
            _rows=lambda ref, start, size: ref[:, pl.ds(0, size), :])
    return out


def median_ms(fn, calls):
    import jax
    jax.block_until_ready(fn())          # compiles
    jax.block_until_ready(fn())
    times = []
    for _ in range(calls):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t) * 1e3)
    return statistics.median(times), min(times)


def time_site(A, name, calls, label):
    import jax
    import jax.numpy as jnp
    import numpy as np
    site = SITES[name]
    h, hkv, s, dqk, dv, window = (site[n] for n in (
        "h", "hkv", "s", "dqk", "dv", "window"))
    r = np.random.RandomState(7)
    mk = lambda heads, d: jnp.asarray(                    # noqa: E731
        r.randn(1, heads, s, d).astype(np.float32) * 0.5, jnp.bfloat16)
    q, k, v, g = mk(h, dqk), mk(hkv, dqk), mk(hkv, dv), mk(h, dv)
    seed = jnp.zeros((2,), jnp.float32)
    scale = dqk ** -0.5
    for fn in (A._flash_fwd, A._flash_bwd):
        fn.clear_cache()
    out, lse = A._flash_fwd(q, k, v, None, seed, scale, 0.0, True, window)
    kernels = {
        "fwd": lambda: A._flash_fwd(q, k, v, None, seed, scale, 0.0,
                                    True, window),
        "bwd": lambda: A._flash_bwd(q, k, v, None, seed, out, lse, g,
                                    scale, 0.0, True, window),
    }
    schedule = None
    if hasattr(A, "_blocked_schedule"):
        schedule = A._blocked_schedule(h, hkv, s, s, dqk, dv, 2)._asdict()
    for kernel, fn in kernels.items():
        ms, best = median_ms(fn, calls)
        flops, moved = cost(site, kernel)
        least = max(flops / PEAK_FLOPS, moved / PEAK_BYTES) * 1e3
        print(json.dumps({
            "site": name, "kernel": kernel, "variant": label,
            "ms": round(ms, 3), "ms_min": round(best, 3), "calls": calls,
            "flops": flops, "bytes": moved,
            "least_ms": round(least, 3),
            "roofline_share": round(least / ms, 4),
            "tflops": round(flops / ms / 1e9, 2),
            "schedule": schedule,
            "device": jax.devices()[0].device_kind}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sites", nargs="+", default=sorted(SITES),
                    choices=sorted(SITES))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--ablate", action="store_true")
    ap.add_argument("--tiles", help="G:blk_q:blk_k in place of the "
                    "shape's own tile")
    ap.add_argument("--budget-mb", type=float, help="the VMEM model's "
                    "budget, to time a schedule the shape would not take")
    ap.add_argument("--repo", help="another checkout whose kernels to "
                    "time (the parent's)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="interpreted, at a toy length: proves the "
                    "tool's paths, prints no time worth reading")
    args = ap.parse_args()
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.abspath(args.repo) if args.repo else here)
    import jax
    from paddle_tpu.ops.pallas import attention as A
    if args.rehearse_cpu:
        for site in SITES.values():
            site.update(h=site["h"] // 4, hkv=max(1, site["hkv"] // 4),
                        s=1024, window=site["window"] // 8)
    elif jax.default_backend() != "tpu":
        sys.exit("blocked_sweep: the default backend is %r, not 'tpu'"
                 % jax.default_backend())
    label, patches = "as_chosen", {}
    if args.tiles:
        tiles = tuple(int(x) for x in args.tiles.split(":"))
        patches["_blocked_tiles"] = lambda group, sq, sk: tiles
        label = "tiles=" + args.tiles
    if args.budget_mb:
        patches["_BLOCKED_VMEM_BUDGET"] = int(args.budget_mb * 2 ** 20)
        label += ",budget=%gMB" % args.budget_mb
    with replaced(A, **patches):
        for name in args.sites:
            time_site(A, name, args.calls, label)
            if args.ablate:
                for what, names in ablations(A).items():
                    with replaced(A, **names):
                        time_site(A, name, args.calls,
                                  label + ",without_" + what)


if __name__ == "__main__":
    main()
