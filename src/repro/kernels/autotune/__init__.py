"""repro.kernels.autotune — roofline-driven swap-path kernel autotuner.

Pieces (see docs/kernels.md for the full data flow):

  * :mod:`device` — :class:`DeviceSpec` roofline peaks by device kind
    (shared with the dry-run roofline report);
  * :mod:`space` — per-kernel block-config search spaces + bytes-moved
    accounting;
  * :mod:`tuner` — measures each variant's achieved fraction of the
    memory-bandwidth roofline, keeps the winner;
  * :mod:`cache` — schema-versioned atomic JSON persistence keyed by
    ``(kernel, shape-bucket, dtype, device_kind)``, stored alongside the
    BandwidthModel snapshot (warm restarts re-measure nothing);
  * :mod:`table` — the process-wide tuned-config table the kernel
    wrappers consult (:func:`install` / :func:`tuned_config`);
  * :mod:`advisor` — prices raw-vs-int8 spill compression with the
    tuned numbers (``spill_compression="auto"``).
"""
from __future__ import annotations

from repro.kernels.autotune.advisor import CompressionAdvisor
from repro.kernels.autotune.cache import (AutotuneCache, SCHEMA_VERSION,
                                          cache_key)
from repro.kernels.autotune.device import (DEVICE_SPECS, DeviceSpec,
                                           current_device_kind,
                                           get_device_spec)
from repro.kernels.autotune.table import (clear, install, installed_count,
                                          shape_bucket, table_key,
                                          tuned_config)
from repro.kernels.autotune.tuner import (HOST_LINK_KERNEL, Autotuner,
                                          default_measure)

__all__ = [
    "Autotuner", "AutotuneCache", "CompressionAdvisor", "DeviceSpec",
    "DEVICE_SPECS", "HOST_LINK_KERNEL",
    "SCHEMA_VERSION", "cache_key", "clear", "current_device_kind",
    "default_measure",
    "get_device_spec", "install", "install_cache", "installed_count",
    "shape_bucket", "table_key", "tuned_config",
]


def install_cache(cache: AutotuneCache) -> int:
    """Publish a cache's winners to the process-wide table consulted by
    the kernel wrappers; returns the number of installed configs."""
    entries = cache.table_entries()
    install(entries)
    return len(entries)
