"""What the process runs on: CPU pinning for rehearsals, the persistent
compile cache, and the published peaks of the chips we measure on.

Tests and rehearsals run on the CPU (``JAX_PLATFORMS=cpu``, usually with
eight virtual devices); everything that measures runs on the TPU JAX finds
by default. Nothing here probes for a device or chooses a platform on its
own: a program that needs a chip and finds none fails at its first use.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
from typing import Optional


def pin_cpu_platform(virtual_devices: Optional[int] = None) -> None:
    """Force the CPU backend; optionally expose ``virtual_devices`` host
    devices (the multi-chip simulation used across the test suite).

    Call before the first jax backend use. Safe to call multiple times;
    an existing ``xla_force_host_platform_device_count`` flag is kept.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if virtual_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{virtual_devices}").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


# <checkout>/.jax_cache — a FIXED path: the directory is part of what a
# cache entry is looked up by, so a temp/pid/time-named one never hits
_REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the directory is the
    caller's (JAX reads the variable itself — no other is set in code);
    otherwise it is ``<checkout>/.jax_cache``. Entries are written for
    every program that took over half a second to compile (min entry size
    -1 is what makes the CPU backend write at all). Call before the first
    compilation.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(_REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks a utilization is taken against."""

    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    source: str


# keyed by ``jax.Device.device_kind``. A kind that is not here is an error,
# never a default: a utilization against an assumed peak is not a number.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops_per_s=197e12, hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '819 GB/s HBM per chip'),
}


def device_peaks(device_kind: Optional[str] = None) -> DevicePeaks:
    """Peaks of ``device_kind`` (default: the first device JAX reports)."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add it to "
            f"apex_tpu.utils.platform.DEVICE_PEAKS with its source, or "
            f"pass the peaks explicitly") from None
