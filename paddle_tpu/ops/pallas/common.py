"""Shared helpers for the pallas TPU kernel library.

This package is the analog of the reference's hand-tuned kernel layers
— operators/jit/ (runtime x86 codegen, jit/README.en.md), operators/
fused/ and operators/math/ — re-targeted at the TPU: each kernel is a
pallas Mosaic program registered as a ``library="pallas"`` variant of a
regular op (ops/registry.py register_variant), mirroring the
reference's kernel-type dispatch on library=CUDNN/MKLDNN
(op_kernel_type.h). Every variant keeps the pure-jnp lowering as its
reference implementation (the jit/refer/ pattern) — used for the
backward pass (recompute-style custom_vjp) and as the fallback when
pallas is disabled.

Enable with ``FLAGS_op_library=pallas`` (core/flags.py) or per-run via
Executor internals; tests exercise both paths and compare (the
operators/jit/test.cc pattern).
"""

from __future__ import annotations

import jax

from ...core.flags import FLAGS


def interpret_mode() -> bool:
    """Pallas kernels compile for TPU; everywhere else (CPU unit tests,
    the 8-device virtual mesh) they run in interpreter mode."""
    return jax.default_backend() != "tpu"


def blk(n: int, target: int = 128) -> int:
    """Largest divisor of n that is <= target (block size picker —
    shapes in the models are powers of two, so this lands on 128/64/...;
    degenerate shapes fall back to the full dimension)."""
    if n <= target:
        return n
    for b in range(target, 0, -1):
        if n % b == 0:
            return b
    return n


def count_lowering(name: str, by: float = 1.0) -> None:
    """One bump of the counter ``name`` each time a site is LOWERED
    (trace time, so nothing in a step): ``sdpa_lowering.<path>``,
    ``moe_lowering``, ``kda_lowering`` and ``rotary_lowering.<path>``
    say, in ``counter_values()``, which lowering a site took. The
    abstract pass that infers shapes while the graph is built is left
    out. ``by`` 0 lists the counter without moving it."""
    from ... import framework, profiler
    if not framework.inferring_shapes():
        profiler.bump_counter(name, by)


FLAGS.define("op_library", "",
             "kernel library variant for op lowerings ('' = pure jnp "
             "XLA path, 'pallas' = hand-written TPU kernels)")
