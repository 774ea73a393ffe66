"""Process start-up: platform pinning, compile cache, device placement, peaks.

One process drives the chips it can see: a parent that has touched JAX
holds them, and a child that needs one then fails or hangs.  So everything
that decides WHICH device a piece of the program uses lives here, derived
from what the process can observe (``jax.local_devices()``, the device's
``device_kind``) rather than from options:

- :func:`force_cpu` pins host-side roles and CPU-simulation tools to the CPU
  backend before any backend exists;
- :func:`enable_compile_cache` gives every entry point the same persistent
  compilation cache, placeable from outside via ``JAX_COMPILATION_CACHE_DIR``;
- :func:`role_device` maps server ``i`` / worker ``j`` of an in-process PS
  cluster onto the local devices;
- :data:`DEVICE_PEAKS` is the one table of hardware peaks, keyed by the
  string the chip itself reports.
"""

from __future__ import annotations

import os
import re
from typing import Optional

#: in-checkout compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
#: Derived from this file's location, never from a temp dir, pid or clock: the
#: path is part of the cache key, so a directory that moves never hits.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind`` as read
#: on the chip.  Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
#: bf16, 16 GB HBM at 819 GB/s), quoted in the on-chip-measurement guide.
#: A kind missing here has NO peak: callers print no MFU / roofline figure
#: for it (and chip-facing bench modes refuse to run) instead of guessing.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_gbps": 819.0},
}


def force_cpu(n_devices: int = 0) -> None:
    """Pin this process to the CPU backend (optionally n virtual devices).

    Must run before any jax operation initializes a backend: jax cannot
    switch platforms afterwards, so a late call raises instead of silently
    leaving the process on whatever it already holds.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        want = f"--xla_force_host_platform_device_count={n_devices}"
        if "xla_force_host_platform_device_count" in flags:
            # an inherited count (e.g. the test env's 8) must not override
            # the caller's explicit topology — replace it
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", want, flags
            )
        else:
            flags = (flags + " " + want).strip()
        os.environ["XLA_FLAGS"] = flags
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "force_cpu() called after jax initialized the "
                f"{jax.default_backend()!r} backend"
            )
        return
    jax.config.update("jax_platforms", "cpu")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and nothing is
    changed here.  Unset: the cache goes to ``<checkout>/.jax_cache``.  The
    compile-time threshold drops to zero (the entry-size one already is) so
    the small per-(bucket, batch) programs each ``KVTable`` jits are cached
    too — on a fresh machine a cold run is mostly compiling them.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def role_device(index: int):
    """The local device that server / worker ``index`` keeps its arrays on.

    In-process PS clusters spread over the host's chips round-robin; on one
    chip every role lands on ``jax.local_devices()[0]``.
    """
    import jax

    devices = jax.local_devices()
    return devices[index % len(devices)]


def bytes_in_use(device) -> Optional[int]:
    """Allocator's live bytes on ``device``; None where the backend does not
    report them (the CPU)."""
    stats = device.memory_stats()
    return stats["bytes_in_use"] if stats else None


def device_peaks() -> Optional[dict]:
    """``DEVICE_PEAKS`` entry of the default device, or None if unknown."""
    import jax

    return DEVICE_PEAKS.get(jax.devices()[0].device_kind)


def device_stamp() -> dict:
    """``{"platform", "kind", "count"}`` as jax reports them — the stamp
    every result carries so a CPU run can never pass for a chip run."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }


def require_tpu() -> dict:
    """Fail unless the default device is a TPU with known peaks; returns
    its :func:`device_stamp`."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"this path measures the chip: jax found platform "
            f"{dev.platform!r} ({dev.device_kind!r}), not a TPU"
        )
    if dev.device_kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no peak table entry for device_kind {dev.device_kind!r}; "
            "add it to utils.platform.DEVICE_PEAKS with its source"
        )
    return device_stamp()
