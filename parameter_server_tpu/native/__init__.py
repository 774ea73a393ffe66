"""Native (C++) components, built lazily with g++ and loaded via ctypes.

The reference builds its host-perf-critical paths (text parsers, transport)
in C++; we do the same (SURVEY.md §2 native checklist).  pybind11 is not in
this image, so the ABI is plain ``extern "C"`` + ctypes.

:func:`load` compiles ``src/<name>.cc`` into ``lib/<name>.so`` on first use
(cached; rebuilt when the source is newer; ``lib/`` is git-ignored, so a
fresh checkout builds everything) and returns the loaded CDLL, or ``None``
when no toolchain is available — callers then degrade to their Python
fallbacks so the package works on toolchain-less hosts.  Entry points that
run on the chip do not accept that quietly: they :func:`load` what their
path needs with ``required=True`` (a failed build raises with the
compiler's stderr) and print :func:`loaded`.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_LIB_DIR = os.path.join(os.path.dirname(__file__), "lib")
_CXX = os.environ.get("PS_CXX", "g++")
_FLAGS = ["-O3", "-std=c++17", "-fPIC", "-shared", "-pthread", "-Wall"]

_lock = threading.Lock()
_cache: dict[str, Optional[ctypes.CDLL]] = {}


class NativeBuildError(RuntimeError):
    pass


def _build(name: str) -> str:
    src = os.path.join(_SRC_DIR, f"{name}.cc")
    out = os.path.join(_LIB_DIR, f"{name}.so")
    if not os.path.exists(src):
        raise NativeBuildError(f"no native source {src}")
    if os.path.exists(out) and os.path.getmtime(out) >= os.path.getmtime(src):
        return out
    os.makedirs(_LIB_DIR, exist_ok=True)
    tmp = out + f".tmp{os.getpid()}"
    cmd = [_CXX, *_FLAGS, src, "-o", tmp]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise NativeBuildError(
            f"native build failed: {' '.join(cmd)}\n{proc.stderr[-2000:]}"
        )
    os.replace(tmp, out)  # atomic vs concurrent builders in other processes
    return out


def load(name: str, *, required: bool = False) -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library ``name``.

    Returns None if the toolchain is missing/broken unless ``required``.
    Disable entirely with ``PS_NO_NATIVE=1`` (forces Python fallbacks).
    """
    with _lock:
        if name in _cache and not required:
            return _cache[name]
        if name in _cache and _cache[name] is not None:
            return _cache[name]
        if os.environ.get("PS_NO_NATIVE") and not required:
            _cache[name] = None
            return None
        try:
            path = _build(name)
            lib = ctypes.CDLL(path)
        except (NativeBuildError, OSError) as e:
            if required:
                raise
            _cache[name] = None
            return None
        _cache[name] = lib
        return lib


def loaded() -> dict[str, bool]:
    """Which native libraries this process has tried, and whether each
    loaded (False = its callers are on their Python fallback)."""
    with _lock:
        return {name: lib is not None for name, lib in sorted(_cache.items())}
