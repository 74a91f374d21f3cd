"""C serving ABI for paddle_tpu inference.

Reference parity: ``paddle/fluid/inference/capi_exp/`` (PD_Config /
PD_Predictor / PD_Tensor C API over AnalysisPredictor) and the Go
wrapper ``paddle/fluid/inference/goapi/``.  TPU-native translation: the
engine is the StableHLO artifact executor (``paddle_tpu.inference``),
so the C library embeds CPython and drives it — interpreter lifecycle,
GIL discipline, and buffer marshalling live in ``pd_capi.cc``; the
public header is ``pd_inference_api.h``.

``build()`` compiles ``libpaddle_tpu_capi.so`` on demand with the same
in-repo g++ convention as ``paddle_tpu.native`` (keyed by source hash
and the CPython it embeds, ``native/_build.py``).  C programs link it
directly (see ``demo_main.c``); Go programs use the cgo wrapper in
``paddle_tpu/inference/goapi`` over the same ABI.
"""
from __future__ import annotations

import os
import subprocess
import sys
import sysconfig
import threading

from ...native._build import build_shared, failure_text

__all__ = ["build", "lib_path", "header_path", "available"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "pd_capi.cc")
_HDR = os.path.join(_HERE, "pd_inference_api.h")
_SO = os.path.join(_HERE, "libpaddle_tpu_capi.so")
_lock = threading.Lock()


def header_path() -> str:
    return _HDR


def lib_path() -> str:
    return _SO


def python_link_args() -> list:
    """Compiler args to embed the running CPython: include dir, libdir,
    -lpython, and an rpath so the demo binary finds libpython at run
    time without LD_LIBRARY_PATH."""
    inc = sysconfig.get_config_var("INCLUDEPY")
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION") or sysconfig.get_config_var(
        "VERSION")
    return ["-I" + inc, "-L" + libdir, "-lpython" + ver,
            "-Wl,-rpath," + libdir]


def build() -> bool:
    """Compile libpaddle_tpu_capi.so in-tree unless it is already built
    from these sources for this CPython; True on success.  A failure
    prints the compiler's words to stderr."""
    with _lock:
        try:
            build_shared(_SO, [_SRC, _HDR],
                         ["-fvisibility=hidden", *python_link_args()])
            return True
        except (OSError, subprocess.SubprocessError) as e:
            print("paddle_tpu.inference.capi: build failed "
                  f"({failure_text(e)})", file=sys.stderr)
            return False


def available() -> bool:
    return build()
