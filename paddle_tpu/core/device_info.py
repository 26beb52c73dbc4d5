"""Host + device introspection.

Reference: platform/cpu_info.cc (core counts, cache sizes,
FLAGS_fraction_of_cpu_memory_to_use), platform/gpu_info.cc (device
count, memory fractions). TPU-native: PJRT owns HBM, so this module
reports rather than budgets — memory_stats come from the runtime."""

from __future__ import annotations

import os
from typing import Dict, List, Optional


def cpu_core_count() -> int:
    return os.cpu_count() or 1


def cpu_memory_bytes() -> Optional[int]:
    try:
        pages = os.sysconf("SC_PHYS_PAGES")
        page_size = os.sysconf("SC_PAGE_SIZE")
        return pages * page_size
    except (ValueError, OSError):
        return None


def device_count() -> int:
    import jax
    return jax.device_count()


# the allocator's reserved side: on the TPU runtime a step program's
# temporaries are RESERVED when it first runs and stay so, outside
# ``bytes_in_use``, so the device's peak is ``bytes_in_use +
# peak_bytes_reserved`` where that is the larger
_RESERVED_STATS = ("bytes_reserved", "peak_bytes_reserved",
                   "largest_free_block_bytes")


def device_properties(device_id=0) -> Dict:
    """Kind + memory stats of one device, given as its index in
    ``jax.devices()`` or as the device itself (gpu_info.cc
    GpuMaxAllocSize analog; HBM numbers come straight from PJRT).
    Of ``_RESERVED_STATS`` only what the runtime reports is there."""
    import jax
    d = device_id if hasattr(device_id, "memory_stats") \
        else jax.devices()[device_id]
    props = {
        "device_kind": d.device_kind,
        "platform": d.platform,
        "id": d.id,
        "process_index": d.process_index,
    }
    try:
        stats = d.memory_stats() or {}
        props["bytes_limit"] = stats.get("bytes_limit")
        props["bytes_in_use"] = stats.get("bytes_in_use")
        props["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        props.update((k, stats[k]) for k in _RESERVED_STATS
                     if k in stats)
    except Exception:
        pass  # CPU backend has no memory_stats
    return props


def all_device_properties() -> List[Dict]:
    import jax
    return [device_properties(i) for i in range(jax.device_count())]
