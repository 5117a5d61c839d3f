"""Native (C) runtime components, loaded via ctypes.

Build-on-first-use: cc -O3 -shared compiles the sibling .c into a
cached .so named by the source's hash (atomic rename,
concurrent-build safe). Everything here is
OPTIONAL — callers keep a pure-numpy fallback, so a box without a C
compiler still runs, just with more host time per batch."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import shutil
import subprocess
import tempfile

import numpy as np
from numpy.ctypeslib import ndpointer

logger = logging.getLogger("native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_lib = None
_tried = False


def _build_so() -> str | None:
    src = os.path.join(_DIR, "pack.c")
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:12]
    except OSError:
        return None
    # named by the source's hash: a binary is only ever reused for the
    # pack.c it was built from (mtimes do not survive a copy)
    so = os.path.join(_DIR, f"_pack_{digest}.so")
    if os.path.exists(so):
        return so
    cc = shutil.which("cc") or shutil.which("gcc") or shutil.which("clang")
    if cc is None:
        return None
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_DIR)
    os.close(fd)
    try:
        subprocess.run([cc, "-O3", "-shared", "-fPIC", src, "-o", tmp],
                       check=True, capture_output=True, timeout=60)
        os.replace(tmp, so)  # atomic; concurrent builders all win
        return so
    except Exception as e:  # compiler missing/broken: numpy fallback
        logger.warning("native build failed (%s); using numpy paths", e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def lib():
    """The loaded native library, or None (fallback to numpy)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    so = _build_so()
    if so is None:
        return None
    try:
        L = ctypes.CDLL(so)
        L.tm_pack_pad.restype = None
        L.tm_pack_pad.argtypes = [
            ndpointer(np.uint8, flags="C_CONTIGUOUS"),   # flat
            ndpointer(np.int64, flags="C_CONTIGUOUS"),   # starts
            ndpointer(np.int64, flags="C_CONTIGUOUS"),   # lens
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ndpointer(np.uint8, flags="C_CONTIGUOUS"),   # out
            ndpointer(np.int64, flags="C_CONTIGUOUS"),   # nblocks
        ]
        _lib = L
    except OSError as e:  # pragma: no cover
        logger.warning("native load failed (%s); using numpy paths", e)
    return _lib
