"""Drives ``benchmark/run.py``'s main path (or, with ``--calibrate``,
``benchmark/calibrate.py``'s) in a process of its own with one of the
faults only the DeepSeek-V3 program can have planted in it, for
test_benchmark_correct_kanana2.py and for the chip readings of
``limits/kanana2_s8k_scan.json``. Not a test file.

    python fault_driver_kanana2.py <fault> [--calibrate] -- <arguments>

(``none --calibrate -- --workload kanana2_s8k_scan --reference-only
--stand-ins control_int8`` is how the int8 control is read on the chip:
``calibrate.py`` as it stands, with the references' compiled programs
dropped between the float32 and the int8 pass.)

Faults: ``rotary_left_out`` (latent attention's rotary lanes are plain
lanes: no position reaches a score); ``shared_key_lanes_dropped`` (the
keys lose the 64 rotated lanes every head shares: nought in their
place); ``next_experts`` (the experts after the held ones computed in
their place). The reference is untouched.
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

    from paddle_tpu import layers
    from paddle_tpu.models import deepseek_v3

    if fault == "rotary_left_out":
        layers.rotary_embedding = lambda x, **kw: x
    elif fault == "shared_key_lanes_dropped":
        expand = layers.expand

        def dropped(x, expand_times, name=None):
            # the model's one ``expand`` spreads the shared lanes over
            # the heads
            return expand(layers.scale(x, scale=0.0), expand_times,
                          name=name)
        layers.expand = dropped
    elif fault == "next_experts":
        init = deepseek_v3.DeepseekV3Config.__init__

        @functools.wraps(init)      # the adapter reads its parameters
        def shifted(self, *args, **kwargs):
            init(self, *args, **kwargs)
            self.first_held_expert += self.n_routed_experts
        deepseek_v3.DeepseekV3Config.__init__ = shifted
    elif fault != "none":
        raise SystemExit("unknown fault %r" % fault)
    if "--calibrate" in sys.argv[:sys.argv.index("--")]:
        import jax
        from benchmark import calibrate, run
        from benchmark.reference import common
        following = run.reference_readings

        def one_at_a_time(*args, **kwargs):
            # a loaded executable keeps its temporaries reserved: the
            # float32 and the int8 reference's do not fit side by side
            # next to five copies of 576M parameters
            common._compiled.cache_clear()
            jax.clear_caches()
            return following(*args, **kwargs)
        run.reference_readings = one_at_a_time
        sys.argv = ["calibrate.py"] + argv
        return calibrate.main()
    from benchmark import run
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
