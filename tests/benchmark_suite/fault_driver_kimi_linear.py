"""Drives ``benchmark/run.py``'s main path (or, with ``--calibrate``,
``benchmark/calibrate.py``'s) in a process of its own with one of the
faults only the Kimi Linear program can have planted in it, for
test_benchmark_correct_kimi_linear.py and for the chip readings of
``limits/kimi_linear_s8k_scan.json``. Not a test file.

    python fault_driver_kimi_linear.py <fault> [--calibrate] -- <arguments>

(``none --calibrate -- --workload kimi_linear_s8k_scan --reference-only
--stand-ins control_int8`` is how the int8 control was read on the
chip: ``calibrate.py`` as it stands, with the references' compiled
programs dropped between the float32 and the int8 pass.)

Faults: ``decay_left_out`` (the KDA state never decays: the log decay
that reaches the core is nought); ``beta_left_out`` (the delta rule's
step is 1 for every token and head); ``shared_key_lanes_dropped``
(latent attention's keys lose the 64 lanes every head shares: nought
in their place); ``next_experts`` (the experts after the held ones
computed in their place); ``kda_carry_bf16`` (the state the chunked
delta rule carries from chunk to chunk kept in bfloat16: the planted
lower precision that is this model's own). The reference is untouched.

At the rehearsal's 32 tokens a row one chunk of 64 would hold the whole
row and carry nothing, so ``kda_carry_bf16`` there also cuts the chunk
to 8 tokens (sub-blocks of 4): the chunked form is the same model at
any chunk.
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

    import jax.numpy as jnp
    from paddle_tpu import layers
    from paddle_tpu.models import kimi_linear
    from paddle_tpu.ops import kda_ops

    if fault == "decay_left_out":
        core = layers.kda_attention

        def no_decay(q, k, v, g, beta, **kw):
            return core(q, k, v, layers.scale(g, scale=0.0), beta, **kw)
        layers.kda_attention = no_decay
    elif fault == "beta_left_out":
        core = layers.kda_attention

        def no_beta(q, k, v, g, beta, **kw):
            one = layers.scale(beta, scale=0.0, bias=1.0)
            return core(q, k, v, g, one, **kw)
        layers.kda_attention = no_beta
    elif fault == "shared_key_lanes_dropped":
        expand = layers.expand

        def dropped(x, expand_times, name=None):
            # the model's one ``expand`` spreads the shared lanes over
            # the heads
            return expand(layers.scale(x, scale=0.0), expand_times,
                          name=name)
        layers.expand = dropped
    elif fault == "next_experts":
        init = kimi_linear.KimiLinearConfig.__init__

        @functools.wraps(init)      # the adapter reads its parameters
        def shifted(self, *args, **kwargs):
            init(self, *args, **kwargs)
            self.first_held_expert += self.num_experts
        kimi_linear.KimiLinearConfig.__init__ = shifted
    elif fault == "kda_carry_bf16":
        kda_ops._STATE_DTYPE = jnp.bfloat16
        if "--rehearse-cpu" in argv:
            kda_ops._CHUNK, kda_ops._SUB = 8, 4
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
            # next to five copies of 602M parameters
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
