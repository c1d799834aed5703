"""Native (C++) host-runtime components.

The reference delegates its host-side heavy lifting to JVM dependencies
(Spark data movement, HBase scans); here the equivalent hot host paths are
small C++ libraries loaded via ctypes, with the Python implementation as
both the fallback and the behavioral oracle:

- ``jsonl_codec``: bulk event import/export codec (data/loader plane;
  replaces ``tools/.../imprt/FileToEvents.scala:41-103``'s Spark job).

Build: compiled on demand with g++ into ``_build/`` next to this file
(no pybind11 — plain C ABI). The library's file name carries the sha256
of its source, so what is loaded is decided by ``src/*.cpp`` alone: a
``_build/`` left over from other source (a copied tree keeps no mtimes)
is never picked up. ``PIO_NATIVE_DISABLE=1`` forces the pure Python
paths; a failed build logs a warning and the callers take them too.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger("pio.native")

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_BUILD_DIR = os.path.join(os.path.dirname(__file__), "_build")

_lock = threading.Lock()
_cache: dict = {}


def _build(name: str) -> Optional[str]:
    """Compile src/<name>.cpp -> _build/lib<name>-<source sha256>.so
    unless that exact file exists; None on failure (no toolchain,
    read-only install, ...)."""
    src = os.path.join(_SRC_DIR, f"{name}.cpp")
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except FileNotFoundError:
        return None
    out = os.path.join(_BUILD_DIR, f"lib{name}-{digest}.so")
    if os.path.exists(out):
        return out
    try:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # build under a private name, publish with an atomic rename: a
        # concurrent process never loads a half-written library
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
               "-o", tmp, src]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=120)
        if proc.returncode != 0:
            logger.warning("native build of %s failed:\n%s", name,
                           proc.stderr[-2000:])
            return None
        os.replace(tmp, out)
        return out
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning("native build of %s failed: %s", name, e)
        return None


def load(name: str) -> Optional[ctypes.CDLL]:
    """Load (building if needed) lib<name>; None if unavailable."""
    if os.environ.get("PIO_NATIVE_DISABLE") == "1":
        return None
    with _lock:
        if name in _cache:
            return _cache[name]
        lib = None
        path = _build(name)
        if path is not None:
            try:
                lib = ctypes.CDLL(path)
            except OSError as e:
                logger.warning("failed to load %s: %s", path, e)
        _cache[name] = lib
        return lib


def available(name: str) -> bool:
    return load(name) is not None
