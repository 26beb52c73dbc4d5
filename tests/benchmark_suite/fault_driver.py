"""Drives ``benchmark/run.py``'s main path in a process of its own with
the timed path broken underneath (or the control in the program's
place), for test_benchmark_correct.py. Not a test file.

    python fault_driver.py <fault> [--no-dropout] -- <run.py arguments>

Faults: ``none``; ``state_unchanged`` (every dispatch returns its state
as it found it); ``half_batch`` (half of the batch left out, the mean
taken over the rest); ``no_exchange`` (every chip of four is fed the
first chip's rows: what the first computes alone is all the exchange
can average); ``control_int8`` (the reference in int8 stands in the
program's place). ``--no-dropout`` zeroes every dropout rate of the
configuration, and the comparison takes the limits the cell's file
keeps for that (``rehearsal_no_dropout``): at toy widths int8's error
is a tenth of what it is at the cell's own, and the masks' noise would
bury it.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    fault = sys.argv[1]
    rest = sys.argv[2:]
    no_dropout = "--no-dropout" in rest
    argv = rest[rest.index("--") + 1:]

    import numpy as np
    from benchmark import compare, run
    from benchmark.adapters import paddle_static

    seen = {}
    load_cell = run.load_cell

    def patched_load_cell(workload, rehearsal=False):
        c = load_cell(workload, rehearsal)
        if no_dropout:
            for k in c["args"]:
                if "dropout" in k:
                    c["args"][k] = 0.0
        seen["cell"] = c
        return c

    run.load_cell = patched_load_cell
    if no_dropout:
        load_limits = compare.load_limits
        compare.load_limits = lambda workload: {
            "rehearsal": load_limits(workload)["rehearsal_no_dropout"]}
    System = paddle_static.System
    set_batch, dispatch = System.set_batch, System.dispatch

    if fault == "half_batch":
        def cut(self, batch):
            n = next(iter(batch.values())).shape[0] // 2
            set_batch(self, {k: v[:n] for k, v in batch.items()})
        System.set_batch = cut
    elif fault == "no_exchange":
        def same_rows(self, batch):
            n = next(iter(batch.values())).shape[0] // 4
            set_batch(self, {k: np.concatenate([v[:n]] * 4)
                             for k, v in batch.items()})
        System.set_batch = same_rows
    elif fault == "state_unchanged":
        def frozen(self):
            import jax.numpy as jnp
            names = [n for n in self.scope.local_var_names()
                     if self.scope.find_var(n) is not None]
            saved = {n: jnp.copy(self.scope.find_var(n)) for n in names}
            out = dispatch(self)
            for n, v in saved.items():
                self.scope.set_var(n, v)
            return out
        System.dispatch = frozen
    elif fault == "control_int8":
        build_system = run.build_system

        def remember(c, seed, devices):
            system, batch, stats = build_system(c, seed, devices)
            seen.update(batch=batch, seed=seed)
            return system, batch, stats

        first_dispatches = run.first_dispatches

        def stand_in(system, n):
            real = first_dispatches(system, n)
            low = run.reference_readings(seen["cell"], seen["batch"],
                                         seen["seed"], mode="int8")
            return {"loss": low["loss"], "first": low["first"],
                    "last": low["last"],
                    "loss_scaling": real["loss_scaling"]}
        run.build_system = remember
        run.first_dispatches = stand_in
    elif fault != "none":
        raise SystemExit("unknown fault %r" % fault)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
