"""Core substrate: scope, flags, errors, places.

Reference: the pybind ``core`` module (paddle/fluid/pybind/pybind.cc) +
platform/ (place.h, device_context.h). Device identity on TPU is a JAX
device or a mesh position; DeviceContext/stream management is owned by
PJRT/XLA, so Places here are lightweight tags for API parity.
"""

from __future__ import annotations

import jax

from .enforce import (AlreadyExistsError, EnforceNotMet,  # noqa: F401
                      InvalidArgumentError, NotFoundError,
                      OutOfRangeError, PreconditionNotMetError,
                      UnimplementedError, enforce, enforce_not_none)
from .flags import FLAGS  # noqa: F401
from .scope import Scope, global_scope  # noqa: F401


class CPUPlace:
    """Host place (reference: platform/place.h:26)."""

    def __repr__(self):
        return "CPUPlace"

    def __eq__(self, other):
        return isinstance(other, CPUPlace)


class TPUPlace:
    """Device place (TPU analog of CUDAPlace, place.h:37)."""

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return "TPUPlace(%d)" % self.device_id

    def __eq__(self, other):
        return (isinstance(other, TPUPlace)
                and other.device_id == self.device_id)


# CUDA-name alias for source compatibility with reference user scripts.
CUDAPlace = TPUPlace


class CUDAPinnedPlace:
    """Pinned host staging (place.h:52); host-side infeed buffers."""

    def __repr__(self):
        return "CUDAPinnedPlace"


# Published bf16 peak matmul FLOP/s per chip, keyed by PJRT
# ``device_kind`` (Google Cloud TPU documentation). The chips this
# framework knows: anything that reports a utilization, or vouches that
# it ran on a chip, looks the device up here, and a kind that is absent
# is an error, never a default.
TPU_PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,   # v6e
    "TPU v6e": 918e12,
}


def get_devices():
    return jax.devices()


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return any(d.platform != "cpu" for d in jax.devices())
