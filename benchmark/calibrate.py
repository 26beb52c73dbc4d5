#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, on the chip at the
cell's own size, several seeds in ONE process:

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 \
        --extra-seeds 3 [--first-seed N] [--rehearse-cpu]

For every seed: the program's first dispatches (one system, re-seeded:
fresh optimizer state, the seed's weights and batch) against the
float32 reference -- the LOWER readings. For the first ``--extra-seeds``
seeds also, each put in the program's place against the same
reference: the control (the reference in int8, the precision below the
bf16 the configurations state that v5e's matrix unit runs faster), the
float32 reference with other dropout masks (what a sound program may
differ by), and the planted faults (half of the batch left out, the
mean taken over the rest; a quarter kept, as one of four chips that
skips the exchange sees). One JSON line per reading, each with the
verdict ``compare.decide`` gives it under the cell's chip limits as
committed; the file goes to ``chiprun_out/``. ``--reference-only``
leaves the program out: the stand-ins are the reference against itself
and need one chip.

    python3 benchmark/calibrate.py --workload <cell> --judge FILE

reads such a file again and prints every reading's verdict under the
limits as they are now: the path by which a limit set after the
readings is shown to pass the program and fail the control.

Not run by the benchmark's own runs.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


# what stands in the program's place: (precision mode, the share of the
# batch's rows kept, the stream the dropout masks are drawn from)
STAND_INS = {
    "other_masks": ("f32", None, 2),       # the masks' own noise
    "control_int8": ("int8", None, 1),
    "fault_half_batch": ("f32", 2, 1),
    "fault_quarter_batch": ("f32", 4, 1),
}


def verdict(values, limits):
    """What ``run.py`` would print for these readings: ``correct`` and
    the numbers over their limits."""
    from benchmark import compare
    ok, compared = compare.decide(values, limits)
    return {"correct": ok,
            "over": sorted(n for n, c in compared.items()
                           if not c["value"] <= c["limit"])}


def judge(path, limits):
    """Every reading of a calibration file under ``limits``; returns
    {what: [correct, ...]} in the file's order."""
    seen = {}
    for rec in map(json.loads, open(path)):
        v = verdict(rec["values"], limits)
        seen.setdefault(rec["what"], []).append(v["correct"])
        print(json.dumps({"seed": rec["seed"], "what": rec["what"], **v,
                          "compared": {n: rec["values"][n]
                                       for n in limits}}))
    return seen


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--extra-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2200000001)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--stand-ins", default=",".join(STAND_INS))
    ap.add_argument("--reference-only", action="store_true",
                    help="no program: only the stand-ins against the "
                    "float32 reference, which need one chip whatever "
                    "the cell asks for")
    ap.add_argument("--judge", default=None, metavar="FILE",
                    help="no run: the verdicts of FILE's readings "
                    "under the cell's limits as they are now")
    a = ap.parse_args()

    from benchmark import compare, run

    limits = compare.load_limits(a.workload)[
        "rehearsal" if a.rehearse_cpu else "chip"]
    if a.judge:
        seen = judge(a.judge, limits)
        print(json.dumps({w: "%d of %d correct" % (sum(v), len(v))
                          for w, v in seen.items()}))
        return
    c = run.load_cell(a.workload, a.rehearse_cpu)
    devices = run.find_devices(1 if a.reference_only else c["chips"],
                               a.rehearse_cpu)
    n_compare = int(c["traffic"]["compare_dispatches"])
    out_path = a.out or os.path.join(
        ROOT, "chiprun_out", "calibrate.%s.jsonl" % a.workload)
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    out = open(out_path, "a")

    leaves = open(out_path + ".leaves", "a")

    def emit(rec, stand=None, ref=None):
        rec.update(verdict(rec["values"], limits))
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()
        if stand is not None:       # every leaf's numbers, for a look
            leaves.write(json.dumps({
                "seed": rec["seed"], "what": rec["what"],
                "stand": {"loss": stand["loss"], "first": stand["first"],
                          "last": stand["last"]},
                "reference": ref}) + "\n")
            leaves.flush()

    n_rows = int(c["traffic"]["batch"])

    def stand_ins(seed, batch, ref):
        for what in a.stand_ins.split(","):
            mode, rows_over, stream = STAND_INS[what]
            t3 = time.perf_counter()
            stand = run.reference_readings(
                c, batch, seed, mode=mode, mask_stream=stream,
                rows=n_rows // rows_over if rows_over else None)
            v, n = compare.readings(stand, ref)
            emit({"workload": a.workload, "seed": seed, "what": what,
                  "values": v, "notes": n,
                  "loss": [stand["loss"], ref["loss"]],
                  "seconds": time.perf_counter() - t3}, stand)

    if a.reference_only:        # build_system sets it otherwise
        import jax
        jax.config.update("jax_default_prng_impl",
                          c["config"]["training"]["prng_impl"])
    system = None
    for i in range(a.seeds):
        seed = a.first_seed + 7919 * i
        t0 = time.perf_counter()
        if a.reference_only:
            if i >= a.extra_seeds:
                break
            batch, _ = run.make_batch(c, seed)
            ref = run.reference_readings(c, batch, seed)
            stand_ins(seed, batch, ref)
            continue
        if system is None:
            system, batch, _ = run.build_system(c, seed, devices)
        else:
            batch, _ = run.make_batch(c, seed)
            system.reseed(seed)
            system.set_batch(batch)
        program = run.first_dispatches(system, n_compare)
        t1 = time.perf_counter()
        ref = run.reference_readings(c, batch, seed)
        t2 = time.perf_counter()
        values, notes = compare.readings(program, ref)
        m1p, m1r = program["first"]["m1"], ref["first"]["m1"]
        ratio = sorted((m1p[n] / max(m1r[n], 1e-30), n) for n in m1r)
        emit({"workload": a.workload, "seed": seed,
              "what": "program", "values": values, "notes": notes,
              "loss_scaling": program["loss_scaling"],
              "m1_ratio_quartiles": [ratio[len(ratio) * q // 4][0]
                                     for q in (0, 1, 2, 3)]
              + [ratio[-1][0]],
              "loss": [program["loss"], ref["loss"]],
              "program_s": t1 - t0, "reference_s": t2 - t1},
             program, ref)
        if i < a.extra_seeds:
            stand_ins(seed, batch, ref)
    out.close()


if __name__ == "__main__":
    main()
