"""Shared build-on-demand loader for the in-tree C++ libraries.

One implementation of the compile/mtime-cache/CDLL/lock dance for every
native module (bngring, bngxsk, ...): the reference gets this from its
Makefile + cgo; here the .so is compiled from native/*.cpp on first use
(`*.so` is git-ignored: a checkout has no library until then). Where no
toolchain exists, `load` returns None and callers take their Python/stub
paths — logged once per library, never in silence.
"""

from __future__ import annotations

import ctypes as C
import os
import subprocess
import threading
from typing import Callable

_HERE = os.path.dirname(os.path.abspath(__file__))
SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(_HERE)), "native")

_libs: dict[str, object] = {}
_lock = threading.Lock()


def _build(src: str, so_path: str) -> str:
    """Path of an up-to-date .so, building it if stale. Raises OSError /
    SubprocessError with the reason when it cannot."""
    if not os.path.exists(src):
        raise FileNotFoundError(src)
    if (os.path.exists(so_path)
            and os.path.getmtime(so_path) >= os.path.getmtime(src)):
        return so_path
    cmd = ["g++", "-O2", "-g", "-Wall", "-fPIC", "-std=c++17", "-shared",
           "-o", so_path, src]
    subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    return so_path


def load(src_name: str, configure: Callable[[C.CDLL], None]):
    """Load (building if stale) native/<src_name>.cpp as a CDLL.

    configure(lib) declares argtypes/restypes once. Returns the cached
    CDLL, or None (logged, and remembered) when the source or toolchain
    is unavailable.
    """
    with _lock:
        if src_name in _libs:
            return _libs[src_name]
        src = os.path.join(SRC_DIR, f"{src_name}.cpp")
        so_path = os.path.join(_HERE, f"lib{src_name}.so")
        try:
            lib = C.CDLL(_build(src, so_path))
        except (OSError, subprocess.SubprocessError) as e:
            from bng_tpu.utils.structlog import get_logger

            get_logger("nativelib").warning(
                "native library unavailable, Python path in use",
                lib=src_name, error=f"{type(e).__name__}: {e}")
            _libs[src_name] = None  # decided once: no rebuild per call
            return None
        configure(lib)
        _libs[src_name] = lib
        return lib
