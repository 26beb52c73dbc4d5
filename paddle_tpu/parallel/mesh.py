"""Device mesh management.

Reference: the reference manages device groups through NCCL communicator
maps — platform/nccl_helper.h:90 ``NCCLContextMap`` (one comm per
device) and :179 ``MultiNCCLContextMap`` (flat + hierarchical inter/
intra-node comm sets), bootstrapped by gen_nccl_id_op.cc.

TPU-native redesign: a named ``jax.sharding.Mesh`` replaces communicator
maps entirely. Axis names declare *roles* (dp/tp/pp/sp/ep); collectives
are inserted by the XLA GSPMD partitioner from sharding annotations and
ride ICI within a slice and DCN across slices. The hierarchical-allreduce
configuration of the reference corresponds to a 2-D ("dcn", "ici") mesh
layout where jax places the slower axis over DCN automatically.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.enforce import InvalidArgumentError, enforce

# Canonical axis names, in nesting order (outermost first). dp=data,
# pp=pipeline, tp=tensor/model, sp=sequence/context, ep=expert.
AXIS_ORDER = ("dp", "pp", "ep", "sp", "tp")


def make_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None
              ) -> Mesh:
    """Create a Mesh with named axes, e.g. make_mesh({"dp": 4, "tp": 2}).

    Axis sizes must multiply to the device count. ``tp`` (and ``sp``)
    are placed innermost so they map to the fastest ICI links.
    """
    devices = list(devices if devices is not None else jax.devices())
    names = [a for a in AXIS_ORDER if a in axes]
    extra = [a for a in axes if a not in AXIS_ORDER]
    names += extra
    sizes = [axes[a] for a in names]
    total = int(np.prod(sizes)) if sizes else 1
    enforce(total == len(devices),
            "mesh axes %s multiply to %d but %d devices are available",
            axes, total, len(devices))
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def data_parallel_mesh(num_devices: Optional[int] = None) -> Mesh:
    n = num_devices or jax.device_count()
    return make_mesh({"dp": n}, jax.devices()[:n])


# THREAD-LOCAL ambient mesh: mesh-aware ops (ring/zigzag/ulysses/moe,
# and the sdpa sp routing) read it at trace time, and every trace
# happens in the thread that entered mesh_guard (CompiledProgram.run
# traces synchronously inside its guard). A process-global here would
# let one thread's mesh silently reroute an UNRELATED program being
# traced concurrently on another thread (a serving process hosting a
# mesh model next to a plain one) through a schedule it never opted
# into.
import threading as _threading

_mesh_tls = _threading.local()


def set_mesh(mesh: Optional[Mesh]):
    _mesh_tls.mesh = mesh


def current_mesh() -> Optional[Mesh]:
    return getattr(_mesh_tls, "mesh", None)


def in_manual_body() -> bool:
    """Whether tracing is inside a ``shard_map`` body (the pipeline's,
    sequence parallelism's, a kernel's per-shard wrapper, a user's):
    there the arrays are already one shard's, and building another
    ``shard_map`` would nest one."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def _key_partitions(key) -> bool:
    """Whether XLA's partitioner splits ``key``'s bit generator by
    shard: threefry with ``jax_threefry_partitionable`` on computes
    each element's bits from its index alone. An ``rbg`` key lowers to
    one ``rng-bit-generator`` whose stream depends on the whole shape,
    so a GSPMD program draws the global array on every device and
    slices it."""
    return (jax.random.key_impl(key) == "threefry2x32"
            and jax.config.jax_threefry_partitionable)


def per_shard_batch_axis(batch: int, key=None) -> Optional[str]:
    """The mesh axis an op's per-shard body splits a leading axis of
    ``batch`` rows over, or None where the op stays one global program.
    "dp" under a multi-device ambient mesh whose ``dp`` axis (of more
    than one device) divides ``batch``, outside any manual body. With
    ``key``, an op drawing random bits from it: only where the key's
    generator does not partition by itself (``_key_partitions``), so
    that drawing per shard saves the global draw."""
    mesh = current_mesh()
    if mesh is None or mesh.size <= 1 or in_manual_body():
        return None
    if key is not None and _key_partitions(key):
        return None
    n = dict(mesh.shape).get("dp", 1)
    return "dp" if n > 1 and batch % n == 0 else None


@contextlib.contextmanager
def mesh_guard(mesh: Mesh):
    prev = current_mesh()
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def named_sharding(mesh: Mesh, spec: Optional[PartitionSpec]
                   ) -> NamedSharding:
    return NamedSharding(mesh, spec if spec is not None
                         else PartitionSpec())


def shard_batch_spec(ndim: int, axis_name: str = "dp") -> PartitionSpec:
    """Shard dim 0 (batch) over the data axis, replicate the rest."""
    return PartitionSpec(axis_name, *([None] * (ndim - 1)))


def first_divisible_dim(shape: Tuple[int, ...], parts: int
                        ) -> Optional[int]:
    for i, d in enumerate(shape):
        if d is not None and d > 0 and d % parts == 0:
            return i
    return None
