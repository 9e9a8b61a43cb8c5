"""On-demand build + load of the native C++ parser extension.

The reference ships its parser stack as C++ (src/io/parser.cpp); here the
native module is compiled once per interpreter ABI with plain g++ against
the CPython headers (no pybind11 dependency) into this package directory,
then dlopen'd as a normal extension module. The .so is a build product
(git-ignored): a fresh checkout holds none and builds its own on first
use. A missing toolchain or failed build is logged once as a Warning;
callers then parse with the pure-numpy path in io/parser.py, which reads
the same files to the same values, only slower.
"""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import sysconfig
from typing import Optional

from ..utils.log import Log

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "parser.cpp")
_cached = None  # None = not tried, False = unavailable, module otherwise


def _so_path() -> str:
    tag = sysconfig.get_config_var("EXT_SUFFIX") or ".so"
    return os.path.join(_DIR, f"_lgbt_parser{tag}")


def _build() -> Optional[str]:
    so = _so_path()
    if (os.path.exists(so)
            and os.path.getmtime(so) >= os.path.getmtime(_SRC)):
        return so
    include = sysconfig.get_paths()["include"]
    # build to a per-process temp file + atomic rename: concurrent workers
    # (lightgbm_tpu.launch) must never dlopen a half-written .so
    tmp = f"{so}.build.{os.getpid()}"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-pthread", f"-I{include}",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as exc:
        detail = getattr(exc, "stderr", b"") or b""
        Log.warning("native parser not built (%r%s); text files are parsed "
                    "by the slower pure-numpy path", exc,
                    ": " + detail.decode(errors="replace")[-300:]
                    if detail else "")
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    return so


def get_parser():
    """The compiled _lgbt_parser module, or None when unavailable."""
    global _cached
    if _cached is not None:
        return _cached or None
    if os.environ.get("LIGHTGBM_TPU_NO_NATIVE"):
        _cached = False
        return None
    so = _build()
    if so is None:
        _cached = False
        return None
    try:
        spec = importlib.util.spec_from_file_location("_lgbt_parser", so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules["_lgbt_parser"] = mod
        _cached = mod
    except Exception:  # noqa: BLE001
        _cached = False
        return None
    return _cached
