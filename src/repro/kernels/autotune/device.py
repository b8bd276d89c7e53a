"""Device roofline specs (one source of truth).

``launch/roofline.py`` and the kernel autotuner read one
:class:`DeviceSpec` selected by device kind, so the dry-run roofline
report and the autotuner's achieved-vs-peak efficiency are judged
against the same peaks.  The kind is read from the device JAX reports
(``jax.devices()[0].device_kind``, mapped onto the registry by
:data:`JAX_DEVICE_KINDS`); a kind the registry does not know is an
error, never a silent fallback to another chip's peaks.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class DeviceSpec:
    """Peak rates used as roofline denominators (bytes/s, FLOP/s)."""
    kind: str
    peak_flops: float            # bf16 matmul peak
    hbm_bw: float                # HBM bytes/s
    ici_bw: float                # per-link interconnect bytes/s
    host_bw: float               # host<->device link bytes/s

    def to_dict(self) -> dict:
        return asdict(self)


DEVICE_SPECS: Dict[str, DeviceSpec] = {
    # Google Cloud "TPU v5e" page: 197 TFLOP/s bf16, 819 GB/s HBM;
    # ~50 GB/s/link ICI, 32 GB/s host link (the paper's Eq. 3 constant)
    "tpu_v5e": DeviceSpec("tpu_v5e", 197e12, 819e9, 50e9, 32e9),
    "tpu_v5p": DeviceSpec("tpu_v5p", 459e12, 2765e9, 100e9, 32e9),
    "tpu_v4": DeviceSpec("tpu_v4", 275e12, 1228e9, 50e9, 32e9),
    # CPU interpret-mode runs: the peaks are nominal (one memory channel
    # class); efficiencies measured against them are tiny and honest
    "cpu": DeviceSpec("cpu", 1e12, 50e9, 10e9, 32e9),
}

# what ``jax.devices()[0].device_kind`` reports -> registry row
JAX_DEVICE_KINDS: Dict[str, str] = {
    "TPU v5 lite": "tpu_v5e",
    "TPU v5": "tpu_v5p",
    "TPU v4": "tpu_v4",
    "cpu": "cpu",
}


def registry_kind(kind: str) -> str:
    """Registry key for a registry key or a JAX ``device_kind`` string;
    raises ``ValueError`` on a kind the registry does not know."""
    if kind in DEVICE_SPECS:
        return kind
    try:
        return JAX_DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(
            f"unknown device kind {kind!r}: add its peaks to DEVICE_SPECS "
            f"and its JAX name to JAX_DEVICE_KINDS") from None


def current_device_kind() -> str:
    """Registry key of the device this process runs on."""
    import jax
    return registry_kind(jax.devices()[0].device_kind)


def get_device_spec(kind: Optional[str] = None) -> DeviceSpec:
    """Spec for ``kind`` (a registry key or a JAX ``device_kind``), or for
    the device this process runs on when ``kind`` is None."""
    return DEVICE_SPECS[registry_kind(kind) if kind else current_device_kind()]
