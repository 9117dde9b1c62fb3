"""Which device the program runs on: forced, required, or looked up.

Four entry-point helpers, none of which library code calls on import:

- ``force_cpu_devices(n)`` — a CPU backend with ``n`` virtual devices, the
  substrate of the tests and the multi-chip dry run.
- ``require_tpu()`` — the device gate of every measurement path: raises
  unless JAX's first device is a TPU, so a missing chip is an error and
  never a silent CPU (and Pallas-interpreter) run.
- ``enable_compile_cache()`` — JAX's persistent compilation cache at a
  place that can be chosen from outside and never moves on its own.
- ``device_peaks(kind)`` — published peak rates keyed by the
  ``device_kind`` string JAX reports; an unknown kind is an error.
"""

from __future__ import annotations

import os
from typing import NamedTuple

# <checkout>/.jax_cache — derived from this file so every entry point of
# one checkout shares one directory whatever the working directory is (the
# directory is part of the cache key: one that moves never hits).
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def force_cpu_devices(n: int) -> None:
    """Force the CPU backend with ``n`` virtual devices. Call before any
    device use: backends initialize lazily (importing jax is safe,
    touching ``jax.devices()`` is not), and jax raises RuntimeError if the
    backend is already live."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)


def require_tpu() -> tuple[str, str, int]:
    """``(platform, device_kind, device_count)`` of the TPU this process
    holds. Raises RuntimeError naming the platform found when JAX's first
    device is anything else — with no chip attached JAX logs a libtpu
    warning and hands back ``CpuDevice``, and everything downstream would
    carry on interpreted."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise RuntimeError(
            f"this path measures on a TPU and found platform="
            f"{first.platform!r} (device_kind={first.device_kind!r}, "
            f"{len(devices)} device(s)); it does not fall back"
        )
    return first.platform, first.device_kind, len(devices)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. With ``JAX_COMPILATION_CACHE_DIR`` set JAX reads the
    variable itself and nothing is set here; otherwise the cache lives at
    ``<checkout>/.jax_cache``. Entry points call this before their first
    compile; ``import torchkafka_tpu`` never does."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR


class DevicePeaks(NamedTuple):
    """Published per-chip peaks: dense bf16 FLOP/s and HBM bytes/s."""

    bf16_flops: float
    hbm_bytes_s: float


# Keyed by ``jax.devices()[0].device_kind`` exactly as the chip reports it
# (read on the chip in PR 21, PERF.md "Chip bring-up"). Source: Google
# Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM.
_PEAKS = {
    "TPU v5 lite": DevicePeaks(bf16_flops=197e12, hbm_bytes_s=819e9),
}


def device_peaks(device_kind: str | None = None) -> DevicePeaks:
    """Peaks of ``device_kind`` (default: this process's first device).
    A kind that is not in the table raises: a roofline or MFU share
    against another chip's peak is a wrong number, not an estimate."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return _PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind={device_kind!r} "
            f"(known: {sorted(_PEAKS)}); add the chip to "
            "torchkafka_tpu/utils/devices.py with its source"
        ) from None
