"""Drives ``benchmark/run.py``'s main path in a process of its own with
one of the two faults only the AFMoE program can have planted in it,
for test_benchmark_correct_afmoe.py. Not a test file.

    python fault_driver_afmoe.py <fault> -- <run.py arguments>

Faults: ``window_left_out`` (the program's sliding layers attend over
the whole causal row: the ``window`` never reaches the attention op);
``next_experts`` (the program computes the experts after the ones it
holds, in their place: the tokens routed to experts n..2n-1 through the
weights of 0..n-1). The reference is untouched.
"""

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    fault = sys.argv[1]
    argv = sys.argv[sys.argv.index("--") + 1:]

    from benchmark import run
    from paddle_tpu import layers
    from paddle_tpu.models import afmoe

    if fault == "window_left_out":
        sdpa = layers.scaled_dot_product_attention

        def no_window(*args, window=0, **kwargs):
            return sdpa(*args, **kwargs)
        layers.scaled_dot_product_attention = no_window
    elif fault == "next_experts":
        init = afmoe.AfmoeConfig.__init__

        @functools.wraps(init)      # the adapter reads its parameters
        def shifted(self, *args, **kwargs):
            init(self, *args, **kwargs)
            self.first_held_expert += self.num_experts
        afmoe.AfmoeConfig.__init__ = shifted
    else:
        raise SystemExit("unknown fault %r" % fault)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
