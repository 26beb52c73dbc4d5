"""Op library: every op is a pure JAX lowering registered in `registry`.

This package replaces the reference's paddle/fluid/operators/ (~164k LoC
of C++/CUDA, 404 registered ops). Capability classes map as:
  math_ops      <- elementwise/, activation_op, matmul/mul, blas
  tensor_ops    <- reshape/transpose/concat/... manipulation ops
  reduce_ops    <- reduce_ops/
  nn_ops        <- conv, pool, norm, dropout, lookup_table, losses
  sequence_ops  <- sequence_ops/ (LoD -> mask-based, static shapes)
  rnn_ops       <- lstm/gru ops (lax.scan replaces sequence2batch)
  optimizer_ops <- optimizers/
  metric_ops    <- metrics/
  init_ops      <- fill_constant/gaussian_random/... startup ops
  pallas/       <- fused/ + jit/ analog: hand-written TPU kernels
"""

from . import registry  # noqa: F401
from .registry import (all_op_types, get, has, register,  # noqa: F401
                       register_variant)

# Importing the modules registers the ops.
from . import math_ops  # noqa: F401,E402
from . import tensor_ops  # noqa: F401,E402
from . import reduce_ops  # noqa: F401,E402
from . import init_ops  # noqa: F401,E402
from . import nn_ops  # noqa: F401,E402
from . import optimizer_ops  # noqa: F401,E402
from . import metric_ops  # noqa: F401,E402
from . import control_flow_ops  # noqa: F401,E402
from . import sequence_ops  # noqa: F401,E402
from . import rnn_ops  # noqa: F401,E402
from . import beam_search_ops  # noqa: F401,E402
from . import detection_ops  # noqa: F401,E402
from . import quant_ops  # noqa: F401,E402
from . import loss_ops  # noqa: F401,E402
from . import vision_ops  # noqa: F401,E402
from . import fused_ops  # noqa: F401,E402
from . import collective_ops  # noqa: F401,E402
from . import py_func_op  # noqa: F401,E402
from . import pallas  # noqa: F401,E402
from . import kda_ops  # noqa: F401,E402
