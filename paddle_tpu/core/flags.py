"""Typed global flag system.

TPU-native replacement for the reference's gflags-based configuration
(reference: 115 DEFINE_* sites across paddle/fluid; whitelist exported to
Python via core.init_gflags, python/paddle/fluid/__init__.py:136-196).

One typed registry, overridable from the environment as
``FLAGS_<name>=value`` (same spelling the reference uses), readable and
settable from Python at runtime. Flags that gate tracing-time behavior
take effect on the next program compilation.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict

_BOOL_TRUE = {"1", "true", "yes", "on"}


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in _BOOL_TRUE


@dataclass
class _FlagSpec:
    name: str
    default: Any
    parser: Callable[[str], Any]
    help: str


class _Flags:
    def __init__(self):
        self._specs: Dict[str, _FlagSpec] = {}
        self._values: Dict[str, Any] = {}

    def define(self, name, default, help=""):
        if isinstance(default, bool):
            parser = _parse_bool
        elif isinstance(default, int):
            parser = int
        elif isinstance(default, float):
            parser = float
        else:
            parser = str
        self._specs[name] = _FlagSpec(name, default, parser, help)
        env = os.environ.get("FLAGS_" + name)
        self._values[name] = parser(env) if env is not None else default

    def __getattr__(self, name):
        try:
            return self.__dict__["_values"][name]
        except KeyError:
            raise AttributeError("unknown flag %r" % name)

    def __setattr__(self, name, value):
        if name.startswith("_"):
            super().__setattr__(name, value)
            return
        if name not in self._specs:
            raise AttributeError("unknown flag %r" % name)
        self._values[name] = value

    def as_dict(self):
        return dict(self._values)


FLAGS = _Flags()

# Execution / debugging (reference: operator.cc FLAGS_check_nan_inf :950,
# FLAGS_benchmark :946).
FLAGS.define("check_nan_inf", False,
             "After each step, scan fetched outputs for NaN/Inf and raise.")
FLAGS.define("benchmark", False,
             "Block on device completion after every executor run.")
FLAGS.define("cpu_deterministic", True, "Deterministic reductions on host.")
FLAGS.define("infer_shape_debug", False,
             "Log shape-inference failures at op-append time instead of "
             "deferring errors to trace time.")
FLAGS.define("deterministic", True,
             "Ask XLA for deterministic reductions (analog of "
             "cudnn_deterministic / sync_nccl_allreduce).")

# Memory (analog of FLAGS_fraction_of_gpu_memory_to_use etc.; HBM is
# XLA-managed so these only gate host staging buffers).
FLAGS.define("host_pinned_pool_mb", 256,
             "Host staging pool for infeed, in MB.")

# Tracing / profiling.
FLAGS.define("profile_dir", "", "If set, xprof traces are written here.")

# Random.
FLAGS.define("global_seed", 0, "Framework-wide RNG seed (0 = nondeterministic).")

# Distributed.
FLAGS.define("sync_collectives", True,
             "Deterministic collective order (analog of sync_nccl_allreduce).")
FLAGS.define("rpc_deadline", 180000, "DCN RPC deadline ms (parity).")

# Async communicator (reference: python/paddle/fluid/__init__.py:169-176
# communicator_* gflags tuning Communicator::SendThread batching).
FLAGS.define("communicator_max_merge_var_num", 20,
             "Max queued grads merged into one PS send.")
FLAGS.define("communicator_send_queue_size", 20,
             "Trainer-side send queue depth.")

FLAGS.define("sdpa_auto_flash", True,
             "scaled_dot_product_attention's base lowering routes to "
             "the flash pallas kernel inside its envelope (TPU "
             "backend, <=2-byte dtype, dropout active, single-k-block "
             "shapes: Sk <= 512, Sq at most 256 or a multiple of it) — "
             "the reference jit/ pool's best-impl-at-runtime "
             "dispatch. bench.py pins this off for its pure-XLA base "
             "row. Measured in BERT-base S=512 training (PERF.md, "
             "PR 27; ROADMAP D2).")

FLAGS.define("sp_attention", True,
             "scaled_dot_product_attention's base lowering routes "
             "through the sequence-parallel schedules when the ambient "
             "mesh carries an sp axis (parallel/ulysses.py "
             "sequence_parallel_attention): zigzag ring for causal "
             "no-bias shapes, Ulysses all-to-all head re-sharding "
             "otherwise. Off = keep the replicated full-attention "
             "lowering and let GSPMD place it (correct, but the "
             "S^2 score matrix is not sequence-sharded).")

FLAGS.define("ring_flash", True,
             "ring_attention computes each hop's block attention with "
             "the pallas partial-softmax kernels (ops/pallas/ring.py) "
             "so [Sq_loc, Sk_loc] scores stay in VMEM; falls back to "
             "the jnp body when no kernel geometry fits the scoped-"
             "VMEM model (ring.applicable).")

FLAGS.define("lean_xent_grad", True,
             "fused_linear_xent uses the hand-written one-fusion "
             "backward writing dlogits in the input dtype "
             "(ops/fused_ops.py _lean_xent). Off = autodiff of the "
             "composite lowering.")

FLAGS.define("mxu_bias_grad", True,
             "rank-1 bias adds compute their bf16 bias gradient as "
             "ones@dY on the MXU with f32 accumulation instead of "
             "the broadcast-transpose reduce (ops/math_ops.py "
             "_bias_add_vjp) — faster AND closer to the exact f32 "
             "sum.")

FLAGS.define("resnet_s2d_stem", False,
             "ResNet ImageNet stem runs as space_to_depth(2) + "
             "4x4/s1 conv (12 input channels) instead of 7x7/s2 on "
             "3 channels — the numerically-equivalent MLPerf stem "
             "(models/resnet.s2d_stem_weights). Default OFF until "
             "chip-measured in-model.")

FLAGS.define("mxu_ln_grad", False,
             "layer_norm's dScale/dBias column reductions run as "
             "ones@M MXU dots with f32 accumulation (the "
             "mxu_bias_grad treatment extended to the layer-norm "
             "affine tail — ops/nn_ops._ln_affine). Default OFF "
             "until chip-measured in-model.")

FLAGS.define("verify_rewrites", False,
             "Run the static program verifier (paddle_tpu/analysis) "
             "automatically after each executor rewrite — guard "
             "install, sharded-state conversion, PS split, every "
             "trace entry — and raise on error-severity findings. "
             "The analysis plane's debug/verify mode; off (default) "
             "the hooks cost one flag read.")
